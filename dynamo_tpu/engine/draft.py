"""Draft-model speculative decoding — the proposer half.

A small draft model (same tokenizer/vocab as the target) keeps its own
paged KV cache and proposes ``k`` greedy continuations per sequence in
ONE jitted dispatch; the target engine verifies them with its existing
rejection-sampled verify pass (engine/core.py:_spec_impl).  Greedy
point-mass proposals keep the verify rule exact at any temperature, and
seeded streams remain bit-identical with speculation on or off — the
draft only changes WHICH tokens get proposed, never how emitted tokens
are sampled.

TPU shape: the proposer dispatch ingests each row's not-yet-seen tokens
(one S=U forward over the paged draft cache, pow2-bucketed U) and then
runs k-1 single-token steps under ``lax.scan`` — all on device, one
dispatch per engine spec step.  The draft lags the target by exactly the
tokens emitted since its last dispatch, so in steady spec-mode operation
U stays ≤ k+1; a freshly admitted row's first dispatch ingests its whole
prompt (chunked through the same buckets).

Reference parity: the reference inherits draft/eagle speculative modes
from its engines (vLLM); SURVEY §2.4.  The n-gram prompt-lookup proposer
(engine/spec.py) remains the zero-cost default; the draft engages when
the engine is built with one.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DraftProposer"]

_MAX_INGEST_BUCKET = 512  # longest single ingest dispatch (prompt chunks)


class DraftProposer:
    """Owns the draft model's paged cache + per-slot sync state."""

    def __init__(self, model, params, config, num_blocks: Optional[int] = None):
        self.model = model
        self.params = params
        self.config = config
        self.block_size = config.block_size
        nb = num_blocks or config.num_blocks
        # the draft cache follows the engine's cache_dtype: on HBM-tight
        # deployments (8B target + draft on one 16GiB chip) the int8
        # draft cache is part of what makes the pair fit — quantization
        # error only shifts PROPOSALS; the target's verification stays
        # exact either way
        self.cache = model.init_kv_cache(
            nb, config.block_size, config.cache_dtype)
        self._free = list(range(nb))
        self._blocks: dict[int, list[int]] = {}   # slot -> draft block ids
        self._synced: dict[int, int] = {}         # slot -> tokens ingested
        self._fn = jax.jit(self._impl, donate_argnums=(1,),
                           static_argnames=("k",))
        self.dispatches = 0

    # ------------------------------------------------------------- lifecycle
    def release(self, slot: int) -> None:
        """Return a finished/aborted slot's draft blocks to the pool."""
        self._free.extend(self._blocks.pop(slot, ()))
        self._synced.pop(slot, None)

    # ------------------------------------------------------------- device fn
    def _impl(self, params, cache, tokens, positions, block_tables,
              seq_lens, slot_idx, last_idx, active, *, k):
        """Ingest U tokens per row, then draft k greedy tokens.

        tokens/positions/slot_idx: [B, U] (-1-padded slots drop writes);
        seq_lens: [B] context length AFTER ingest; last_idx: [B] index of
        each row's last real ingest token; active: [B] bool.
        Returns (proposals [B, k] int32, cache).
        """
        model, bs = self.model, self.block_size
        b = tokens.shape[0]
        hidden, cache = model.forward(
            params, tokens, positions, cache, block_tables, seq_lens,
            slot_idx,
        )
        h_last = hidden[jnp.arange(b), last_idx]
        tok = jnp.argmax(
            model.compute_logits(params, h_last), axis=-1
        ).astype(jnp.int32)
        # position of the first drafted token = the row's context length
        pos = seq_lens
        m = block_tables.shape[1]

        def step(carry, _):
            cache, tok, pos, lens = carry
            blk = jnp.minimum(pos // bs, m - 1)
            base = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
            slot = jnp.where(active, base * bs + pos % bs, -1)
            hidden, cache = model.forward(
                params, tok[:, None], pos[:, None], cache, block_tables,
                lens + 1, slot[:, None],
            )
            nxt = jnp.argmax(
                model.compute_logits(params, hidden[:, 0]), axis=-1
            ).astype(jnp.int32)
            return (cache, nxt, pos + 1, lens + 1), tok

        (cache, tok, _, _), drafted = jax.lax.scan(
            step, (cache, tok, pos, seq_lens), None, length=k - 1
        ) if k > 1 else ((cache, tok, pos, seq_lens), jnp.zeros((0, b), jnp.int32))
        props = jnp.concatenate([drafted, tok[None]], axis=0)  # [k, B]
        return props.T, cache

    # ---------------------------------------------------------------- propose
    def _grow(self, slot: int, want_tokens: int) -> bool:
        """Ensure the slot's draft block table covers ``want_tokens``.
        All-or-nothing: a row that cannot fully grow takes NOTHING —
        partial grabs would strand pool blocks on rows that can never
        draft, starving every other row until the hoarders finish."""
        ids = self._blocks.setdefault(slot, [])
        need = (max(want_tokens, 1) - 1) // self.block_size + 1
        if need - len(ids) > len(self._free):
            return False
        while len(ids) < need:
            ids.append(self._free.pop())
        return True

    def _dispatch(self, entries, k: int, draft_active: bool) -> np.ndarray:
        """One jitted draft dispatch over ``entries`` = [(req, start, n)]
        rows placed AT THEIR SLOT in a batch padded to max_batch_size —
        fixed shapes, so the executable count is O(log) in the ingest
        bucket, never per-live-batch-size (the churn the target engine
        pads against).  The block table is sliced to the live context
        (pow2 of the widest row) like the verify path.  Returns the
        [B, k] proposals (pad rows garbage — caller indexes by slot)."""
        b = self.config.max_batch_size
        u = 1 << max(0, (max(n for _, _, n in entries) - 1).bit_length())
        m = 1 << max(0, (max(len(self._blocks[req.slot])
                             for req, _, _ in entries) - 1).bit_length())
        tokens = np.zeros((b, u), np.int32)
        positions = np.zeros((b, u), np.int32)
        slot_idx = np.full((b, u), -1, np.int32)
        bt = np.zeros((b, m), np.int32)
        seq_lens = np.zeros(b, np.int32)
        last_idx = np.zeros(b, np.int32)
        active = np.zeros(b, bool)
        for req, start, n in entries:
            i = req.slot
            toks = req.seq.tokens[start:start + n]
            ids = np.asarray(self._blocks[i], np.int32)
            tokens[i, :n] = toks
            positions[i, :n] = np.arange(start, start + n, dtype=np.int32)
            blk = positions[i, :n] // self.block_size
            slot_idx[i, :n] = (ids[blk] * self.block_size
                               + positions[i, :n] % self.block_size)
            bt[i, :len(ids)] = ids
            seq_lens[i] = start + n
            last_idx[i] = n - 1
            active[i] = draft_active
            self._synced[i] = start + n
        # ONE batched host->device upload (engine/core.py:_upload_dispatch
        # convention): per-array jnp.asarray would issue seven transfer
        # round trips, and per-transfer latency is the cost that matters
        # on a remote-attached chip.  They go where the program runs: the
        # draft's params and cache are on the default device, not on the
        # engine's mesh, so the put names no sharding
        up = jax.device_put(
            (tokens, positions, bt, seq_lens, slot_idx, last_idx, active)
        )
        props, self.cache = self._fn(self.params, self.cache, *up, k=k)
        self.dispatches += 1
        return np.asarray(props)

    def propose(self, reqs, k: int, max_blocks_per_seq: int) -> dict[int, list[int]]:
        """Draft up to ``k`` tokens for each RUNNING request.  Returns
        {slot: proposal tokens}; a row the draft cannot serve this round
        (no free blocks / table overflow) is simply absent — the caller
        falls back to the n-gram proposer for it.

        Rows far behind (fresh long prompts) catch up via at most ONE
        batched ingest-only dispatch per call (k=1, proposals discarded,
        all behind rows in one padded batch) and are skipped for
        proposals until caught up — a 32k prompt costs one extra
        dispatch per engine step for a few steps instead of stalling its
        batch-mates behind ~64 serial dispatches in one step.
        """
        rows = []
        behind = []
        for req in reqs:
            slot = req.slot
            total = req.seq.total_tokens
            if total + k > max_blocks_per_seq * self.block_size:
                continue
            if not self._grow(slot, total + k):
                continue
            if total - self._synced.get(slot, 0) > _MAX_INGEST_BUCKET:
                behind.append(req)
            else:
                rows.append(req)
        if behind:
            self._dispatch(
                [(req, self._synced.get(req.slot, 0), _MAX_INGEST_BUCKET)
                 for req in behind],
                k=1, draft_active=False,
            )
            # a row fully caught up by that chunk may draft this round
            rows.extend(
                req for req in behind
                if req.seq.total_tokens - self._synced[req.slot]
                <= _MAX_INGEST_BUCKET
            )
        if not rows:
            return {}
        entries = [
            (req, self._synced.get(req.slot, 0),
             req.seq.total_tokens - self._synced.get(req.slot, 0))
            for req in rows
        ]
        props = self._dispatch(entries, k=k, draft_active=True)
        # the drafted tokens' KV was written at positions seq_lens..+k-1;
        # the NEXT dispatch re-ingests the really-accepted tokens over
        # those slots, so sync state advances only by ingested tokens
        return {req.slot: [int(t) for t in props[req.slot, :k]]
                for req in rows}
