"""EngineCore — the continuous-batching scheduler + executor.

One jitted *unified step* runs both phases (the model's forward handles any
[B, S] of new tokens against the paged cache):

  prefill:  B=1, S=bucketed prompt remainder (prefix-cache hits skipped)
  decode:   B=max_batch_size slots, S=1

All shapes are static: the decode batch is a fixed array of slots (inactive
rows masked via seq_len=0 / slot_idx=-1) and prefill lengths are padded to
power-of-two buckets — so XLA compiles a handful of executables total and
the hot loop never retraces.  The KV cache array is donated through the
step so XLA updates it in place.

Scheduling policy (reference analogue is inside vLLM; ours is explicit):
admit waiting requests into free slots, run at most one prefill step per
iteration (keeps decode ITL bounded), otherwise run one decode step for all
running slots.  Prefix-cache hits shorten prefill via the block manager
(lib/llm/src/kv/manager.rs:31 prepare_prefill_sequence analogue).

With ``unified_token_dispatch`` the prefill/decode alternation collapses:
a turn with work in both phases runs ONE token-budget ragged dispatch
(``_run_unified`` / ``_unified_fn``) — decode rows lead the flat axis as
1-token chunks, prefill spans pack the remainder — so the per-switch
device round-trip disappears (docs/engine_scheduling.md).

Thread-safety: everything here runs on the engine thread; submit()/abort()
are the only cross-thread entry points and only touch thread-safe queues.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import logging
import queue
import threading
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import operands
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.counters import EngineCounts, track_engine
from dynamo_tpu.engine.grammar import (
    INIT_STATE, JsonGrammar, compile_choice_vocab, compile_regex_vocab,
    compose_tables, device_tables, grammar_mask,
)
from dynamo_tpu.engine.request import EngineRequest, RequestState
from dynamo_tpu.engine.sampling import K_MAX, sample_full
from dynamo_tpu.ops.block_copy import gather_blocks_padded, scatter_blocks_inplace
from dynamo_tpu.ops.paged_attention import prefill_program_key
from dynamo_tpu.llm.kv.block_manager import KvBlockManager, NoFreeBlocks
from dynamo_tpu.llm.protocols import FinishReason, LLMEngineOutput
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs import tracing
from dynamo_tpu.obs.metric_names import ENGINE_COUNTS
from dynamo_tpu.obs.perfmodel import perf_model
from dynamo_tpu.utils.mesh import AXIS_DATA, AXIS_MODEL
from dynamo_tpu.obs.timeline import step_timeline
from dynamo_tpu.tokens import STRIDE_BLOCKS, BlockChainMemo

log = logging.getLogger("dynamo_tpu.engine")

__all__ = ["EngineCore", "unified_step", "multi_decode_step",
           "ragged_prefill_step", "unified_token_step"]


def unified_step(
    model, params, cache, tokens, positions, block_tables, seq_lens,
    slot_idx, last_idx, rng, temp, top_k, top_p, prefix_blocks=None,
    k_cand=K_MAX, exact=False, grammar=None, jrows=None, jstate=None,
    jdepth=None, jstack=None, min_p=None, bias_tokens=None, bias_vals=None,
    seeds=None, seed_rows=None, seq_slots=None,
):
    """THE jitted serving step: forward over the paged cache, gather each
    row's last hidden state, project to logits, sample.  Shared by the
    engine hot loop and the driver's compile checks (__graft_entry__.py).

    ``seq_slots`` int32 [B] (a model with ``recurrent_state`` only): the
    engine slot each row sits in, where that model keeps the row's state.
    (``multi_decode_step`` passes none: its rows are the slot array.)

    Returns ((sampled [B], logprob [B], cand_ids [B,C], cand_lps [B,C]),
    cache) — candidate arrays feed OpenAI top_logprobs."""
    hidden, cache = model.forward(
        params, tokens, positions, cache, block_tables, seq_lens, slot_idx,
        prefix_blocks=prefix_blocks,
        **({} if seq_slots is None else {"seq_slots": seq_slots}),
    )
    b = tokens.shape[0]
    last_h = hidden[jnp.arange(b), last_idx]  # [B, Dm]
    logits = model.compute_logits(params, last_h)  # [B, V] f32
    if grammar is not None:
        # JSON mode: mask invalid-next-token logits (engine/grammar.py)
        logits = grammar_mask(logits, grammar, jrows, jstate, jdepth, jstack)
    out = sample_full(logits, rng, temp, top_k, top_p,
                      bias_tokens=bias_tokens, bias_vals=bias_vals,
                      min_p=min_p, seeds=seeds, seed_rows=seed_rows,
                      # fold on the sampled token's absolute position
                      seed_steps=(seq_lens if seeds is not None else None),
                      k_cand=k_cand, exact=exact)
    return out, cache


def multi_decode_step(
    model, params, cache, last_tokens, positions, block_tables, seq_lens,
    limits, rng, temp, top_k, top_p,
    pen_tokens=None, pen_first=None, freq_pen=None,
    pres_pen=None, grammar=None, jrows=None, jstate=None, jdepth=None,
    jstack=None, min_p=None, bias_tokens=None, bias_vals=None,
    seeds=None, seed_rows=None, carry_tokens=None, carry_rows=None,
    *, block_size: int,
    k_cand: int = K_MAX, exact: bool = False,
):
    """THE jitted decode step: one token a row of the slot array, written
    at the slot its block table gives, then logits, the grammar mask, the
    sample.  The host's round trip is hidden by dispatch-ahead, not by
    decoding several tokens a call (``EngineCore._settle``).

    ``limits[i]`` is the max total tokens sequence i has block space for:
    a row at its limit writes no K/V (slot -1 -> dropped).  Rows that are
    not in the dispatch have limits=0.

    With penalties in the batch the sampler reads the generated-token
    buffer as the host built it (``pen_tokens`` [B,T] -1-padded,
    ``pen_first`` first-occurrence mask); without, the four are None.

    ``carry_tokens`` [1,B] is the ``sampled`` output of the decode
    dispatch issued just before this one, still on the device, and
    ``carry_rows`` [B] marks the rows that were in it: those start from
    its sample instead of ``last_tokens``, which the host has not read
    yet.  The engine always passes both (no row marked when nothing is
    carried), so a shape has one executable.

    Returns ((sampled [1,B], logprob [1,B], cand_ids [1,B,C],
    cand_lps [1,B,C]), cache): the leading axis is what ``carry_tokens``
    takes back.
    """
    m = block_tables.shape[1]
    if carry_tokens is not None:
        last_tokens = jnp.where(carry_rows, carry_tokens[-1], last_tokens)
    blk = jnp.minimum(positions // block_size, m - 1)
    base = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    slot = base * block_size + positions % block_size
    slot = jnp.where(positions < limits, slot, -1)
    hidden, cache = model.forward(
        params, last_tokens[:, None], positions[:, None], cache,
        block_tables, seq_lens, slot[:, None],
    )
    logits = model.compute_logits(params, hidden[:, 0])
    if grammar is not None:
        logits = grammar_mask(logits, grammar, jrows, jstate, jdepth, jstack)
    out = sample_full(
        # the key a dispatch's one step always drew
        logits, jax.random.split(rng, 1)[0], temp, top_k, top_p,
        pen_tokens, pen_first, freq_pen, pres_pen,
        bias_tokens=bias_tokens, bias_vals=bias_vals, min_p=min_p,
        seeds=seeds, seed_rows=seed_rows,
        # fold on the sampled token's absolute position
        seed_steps=(positions + 1 if seeds is not None else None),
        k_cand=k_cand, exact=exact,
    )
    return tuple(a[None] for a in out), cache


def ragged_prefill_step(
    model, params, cache, tokens, positions, block_tables, seq_lens,
    slot_idx, seq_ids, seq_starts, row_offsets, last_idx, rng, temp, top_k,
    top_p, prefix_blocks=0, k_cand=K_MAX, exact=False, grammar=None,
    jrows=None, jstate=None, jdepth=None, jstack=None, min_p=None,
    bias_tokens=None, bias_vals=None, seeds=None, seed_rows=None,
):
    """Token-budget ragged prefill step: ONE forward over a flat packed
    token axis ([1, T]) holding several sequences' prefill chunks, then a
    per-SEQUENCE sample — ``last_idx`` [R] gathers each row's last fresh
    hidden state off the flat axis.  The host keeps only final-chunk rows'
    samples (mixed batches: some rows sample with grammar/logprobs/seeded
    RNG, mid-chunk rows discard).

    ``seed_steps`` is each row's absolute end position (``seq_lens``), so
    a seeded row's sampled token is bit-identical to the one the legacy
    single-request dispatch would draw.
    """
    hidden, cache = model.forward(
        params, tokens, positions, cache, block_tables, seq_lens, slot_idx,
        prefix_blocks=prefix_blocks,
        ragged=(seq_ids, seq_starts, row_offsets),
    )
    last_h = hidden[0, last_idx]  # [R, Dm] — flat-axis gather per sequence
    logits = model.compute_logits(params, last_h)  # [R, V] f32
    if grammar is not None:
        logits = grammar_mask(logits, grammar, jrows, jstate, jdepth, jstack)
    out = sample_full(logits, rng, temp, top_k, top_p,
                      bias_tokens=bias_tokens, bias_vals=bias_vals,
                      min_p=min_p, seeds=seeds, seed_rows=seed_rows,
                      seed_steps=(seq_lens if seeds is not None else None),
                      k_cand=k_cand, exact=exact)
    return out, cache


def unified_token_step(
    model, params, cache, tokens, positions, block_tables, seq_lens,
    slot_idx, seq_ids, seq_starts, row_offsets, last_idx, rng, temp, top_k,
    top_p, pen_tokens=None, pen_first=None, freq_pen=None, pres_pen=None,
    *, row_tokens=0, prefix_blocks=0, k_cand=K_MAX, exact=False,
    grammar=None, jrows=None, jstate=None, jdepth=None, jstack=None,
    min_p=None, bias_tokens=None, bias_vals=None, seeds=None,
    seed_rows=None,
):
    """Unified mixed prefill+decode step: ONE forward over a flat packed
    token axis whose first ``row_tokens`` slots hold DECODE rows (one
    fresh token each, written to the cache per row — their in-block
    offsets are arbitrary) and whose remainder holds block-aligned
    prefill chunk spans.  Decode rows are just 1-token chunks to the
    ragged attention: their ``start`` is the full cached context, the
    per-row prefix gather/DMA covers it, and the positionally-exact
    prefix mask handles the partially-filled tail block.

    Per-row sampling preserves the legacy paths' semantics: decode rows
    and final-chunk prefill rows sample (grammar masks, per-request
    seeds folded on the absolute position ``seq_lens``, penalties over
    the host-built generated-token buffers, logit bias, min_p,
    top_logprobs candidates); mid-chunk rows sample garbage the host
    discards.  Seeded/greedy rows are therefore bit-identical to the
    decode and ragged-prefill dispatches they replace
    (tests/test_unified_dispatch.py pins this).
    """
    hidden, cache = model.forward(
        params, tokens, positions, cache, block_tables, seq_lens, slot_idx,
        prefix_blocks=prefix_blocks,
        ragged=(seq_ids, seq_starts, row_offsets),
        ragged_row_tokens=row_tokens,
    )
    last_h = hidden[0, last_idx]  # [R, Dm] — flat-axis gather per row
    logits = model.compute_logits(params, last_h)  # [R, V] f32
    if grammar is not None:
        logits = grammar_mask(logits, grammar, jrows, jstate, jdepth, jstack)
    out = sample_full(logits, rng, temp, top_k, top_p,
                      pen_tokens, pen_first, freq_pen, pres_pen,
                      bias_tokens=bias_tokens, bias_vals=bias_vals,
                      min_p=min_p, seeds=seeds, seed_rows=seed_rows,
                      seed_steps=(seq_lens if seeds is not None else None),
                      k_cand=k_cand, exact=exact)
    return out, cache


@jax.jit
def expert_totals(moe_counts):
    """int32 [C]: a cache's ``moe_counts`` [L, 1, C] summed over its layers,
    a total for each of the model's ``moe_count_keys``.  A buffer of its own:
    the cache is donated to the next dispatch, this is read back with the
    dispatch's outputs."""
    return moe_counts.sum(axis=(0, 1))


# dispatches a refill of the engine's key block serves
KEY_BLOCK = 256


def key_block(key, length):
    """``length`` steps of ``jax.random.split``'s chain at once: the key
    they leave and the keys they draw, one a dispatch, in order.  The split
    is some hundred operations to trace and lower wherever it stands: in
    every serving program it was 0.1 s of set-up a program (PERF.md, PR 55),
    here it is lowered once an engine."""
    return jax.lax.scan(lambda key, _: tuple(jax.random.split(key)), key,
                        length=length)


def packed(impl):
    """``impl`` as the jitted serving calls take it: ``(*resident, keys,
    bufs, layout=, **kw)`` for ``impl(*resident, *operands, **operand_kw,
    **kw)``.  ``bufs`` is a dispatch's small operands as one transfer
    (``EngineCore._upload_dispatch``): the program takes it apart itself,
    and hands ``impl`` the dispatch's key, which it reads from the engine's
    key block at the place the buffer says, where the operands hold
    ``None``.  No program runs ahead of the serving one but ``key_block``,
    once in ``KEY_BLOCK`` dispatches.  The name stays ``impl``'s, and with
    it the compiled module's (``jit__step_impl``: the benchmark finds
    programs by it)."""

    def serve(*args, layout, **kw):
        *resident, keys, bufs = args
        key_at, ops, ops_kw = operands.unpack(bufs, layout)
        key = jax.lax.dynamic_index_in_dim(keys, key_at, keepdims=False)
        ops = (key if a is None else a for a in ops)
        return impl(*resident, *ops, **ops_kw, **kw)

    serve.__name__, serve.__qualname__ = impl.__name__, impl.__qualname__
    return serve


@dataclasses.dataclass
class _Inflight:
    """A dispatch that has been issued and not read back: its outputs
    are still on the device, and ``finish`` is the host work that waits
    for them (append, stop checks, block commits, emits)."""

    kind: str                       # the timeline's dispatch kind
    out: tuple                      # (sampled, logprob, cand_ids, cand_lps)
    finish: Callable[[tuple], None]
    rows: dict                      # slot -> request the program writes KV for
    # may it stay un-read past the turn that issued it?  (the next
    # dispatch then has nothing to ask of the host: EngineCore._settle)
    deferrable: bool = True
    # requests that ended while this dispatch was already issued: their
    # slot and blocks go back once it has been read back
    ended: list = dataclasses.field(default_factory=list)
    # rows / tokens / ctx for the profiler's dyn.readback event of this
    # dispatch (EngineCore._carried); empty with no profiler session
    carried: dict = dataclasses.field(default_factory=dict)
    # what the model had counted once this dispatch ran (``expert_totals``
    # of the cache's ``moe_counts``), on the device; None for a model that
    # counts nothing
    counted: Any = None


# what a dispatch carried, when no profiler session is open to be told
_NOT_PROFILED: dict = {}


class EngineCore:
    def __init__(
        self,
        model: LlamaModel,
        params,
        config: EngineConfig,
        mesh: Optional[jax.sharding.Mesh] = None,
        eos_token_ids: Optional[list[int]] = None,
        grammar: Optional[JsonGrammar] = None,
        draft: Optional[tuple] = None,
    ):
        self.model = model
        self.config = config
        self.mesh = mesh
        # draft-model speculation: (draft_model, draft_params) with the
        # same tokenizer/vocab as the target — proposals come from the
        # draft (engine/draft.py) instead of n-gram lookup; the verify
        # pass is unchanged (greedy point-mass proposals keep it exact)
        self.draft = None
        if draft is not None:
            if config.spec_tokens <= 0:
                # a silently-inactive draft would be a lie to the operator
                raise ValueError(
                    "a draft model requires spec_tokens > 0 "
                    "(--spec-tokens) to ever propose"
                )
            from dynamo_tpu.engine.draft import DraftProposer

            dmodel, dparams = draft
            if dmodel.config.vocab_size != model.config.vocab_size:
                raise ValueError(
                    "draft model must share the target's vocab "
                    f"({dmodel.config.vocab_size} != {model.config.vocab_size})"
                )
            self.draft = DraftProposer(
                dmodel, dparams, config,
                num_blocks=config.draft_num_blocks or None,
            )
        self.eos_token_ids = set(eos_token_ids or [])
        # JSON-mode grammar: compiled tables (host) + lazy device upload.
        # attach_grammar_tokenizer defers the ~1s vocab compile to the
        # first json_mode request instead of every engine start.
        self._grammar = grammar
        self._grammar_tok = None
        self._choice_tables: dict[tuple, object] = {}
        self._gdev_cache: dict[tuple, tuple] = {}
        # a state of fixed size per slot beside the pool (models/
        # hybrid_linear.py): a cached K/V block says nothing about the state
        # at its end, so no block is ever reused for such a model
        self._recurrent = bool(getattr(model, "recurrent_state", False))
        self.prefix_reuse = config.enable_prefix_reuse and not self._recurrent
        if self._recurrent and config.enable_prefix_reuse:
            log.info("prefix reuse is off: %s keeps a recurrent state, "
                     "which no cached block restores", type(model).__name__)
        self.block_manager = KvBlockManager(
            config.num_blocks,
            config.block_size,
            enable_prefix_reuse=self.prefix_reuse,
        )
        # the block chains of prompts already admitted (_admit alone reads
        # and writes it, on the engine's thread): as many strides as the
        # pool's blocks could hold, since a chain the cache cannot keep is
        # not worth remembering
        self._chain_memo = BlockChainMemo(config.num_blocks // STRIDE_BLOCKS)
        cache_dtype = config.cache_dtype or model.config.dtype
        self.cache_quant = str(cache_dtype) == "int8"
        # a cache in a layout of the model's own (ops/latent_cache.py: rows
        # held once, an indexer's keys beside them or not): what moves
        # blocks was written for the K/V pool, so refuse it here, at
        # start-up, rather than move something else
        self._private_cache_layout = bool(
            getattr(model, "private_cache_layout", False))
        if self._private_cache_layout:
            asked = [name for name, on in (
                ("num_host_blocks", config.num_host_blocks > 0),
                ("kv_persist_dir", bool(config.kv_persist_dir)),
                ("cache_dtype=int8", self.cache_quant),
                ("spec_tokens", config.spec_tokens > 0),
                ("sp_prefill_threshold", config.sp_prefill_threshold > 0),
                ("a mesh", mesh is not None),
                # several sequences on one row axis: whose state?
                ("prefill_token_budget",
                 self._recurrent and config.prefill_token_budget > 0),
                ("unified_token_dispatch",
                 self._recurrent and config.unified_token_dispatch)) if on]
            if asked:
                raise ValueError(
                    f"{type(model).__name__} keeps its cache in a layout "
                    "the block movers do not know (ops/latent_cache.py); "
                    f"not supported with it: {', '.join(asked)}")
        # host-RAM offload tier: device-evicted blocks stay restorable
        # (ref kv/reuse.rs + layer.rs copy streams; SURVEY §5 checkpoint row)
        self.host_pool = None
        self._pending_offload: list[tuple[int, int]] = []  # (device bid, seq_hash)
        if config.num_host_blocks > 0:
            if not config.enable_prefix_reuse:
                log.warning(
                    "num_host_blocks=%d ignored: host offload needs "
                    "enable_prefix_reuse=True (blocks are keyed by prefix hash)",
                    config.num_host_blocks,
                )
            else:
                from dynamo_tpu.llm.kv.host_pool import HostKvPool

                self.host_pool = HostKvPool(config.num_host_blocks)
                self.block_manager.offload_sink = (
                    lambda bid, seq_hash, parent: self._pending_offload.append((bid, seq_hash))
                )
                # async store: the engine thread only dispatches the
                # on-device gather (ordered before any overwrite of the
                # evicted ids); the device→host readback + memcpy runs on
                # this thread — the CUDA-copy-stream analogue, so a
                # request never pays another conversation's offload in
                # its own TTFT.  Bounded queue = HBM backpressure: a full
                # queue falls back to a synchronous store.
                self._offload_lock = threading.Lock()
                self._offload_closed = False
                # each queued entry pins an on-device gather snapshot in
                # HBM until the worker's device_get, so backpressure is
                # bounded by total queued BLOCKS (config budget), not
                # entry count — a large eviction burst falls back to the
                # synchronous store instead of pinning hundreds of MB
                self._offload_inflight_blocks = 0
                self._offload_q: queue.Queue = queue.Queue(maxsize=4)
                self._offload_thread = threading.Thread(
                    target=self._offload_worker, name="kv-offload", daemon=True
                )
                self._offload_thread.start()

        # persistent prefix-cache tier (llm/kv/persist.py): host-published
        # blocks spill to a content-addressed disk store; host-pool misses
        # on admission fall through to it, so warm prefixes survive worker
        # restarts and replicate across workers via the coordinator index
        self.persist_store = None
        self._persist_events: "collections.deque" = collections.deque()
        if config.kv_persist_dir:
            if self.host_pool is None:
                log.warning(
                    "kv_persist_dir=%s ignored: the persistent tier stages "
                    "through the host pool (set num_host_blocks > 0 and "
                    "keep enable_prefix_reuse on)", config.kv_persist_dir,
                )
            else:
                from dynamo_tpu.llm.kv.persist import PersistentKvStore

                self.persist_store = PersistentKvStore(
                    config.kv_persist_dir,
                    generation=self._persist_generation(model, cache_dtype),
                    max_bytes=config.kv_persist_max_bytes,
                    ttl_s=config.kv_persist_ttl_s,
                )
                resident = self.persist_store.resident_hashes()
                if resident:
                    # announce what a restart found on disk, so the router
                    # index learns this worker's persist tier once a
                    # publisher attaches (events drain on the engine
                    # thread each step)
                    from dynamo_tpu.llm.kv.events import TIER_PERSIST, KvStoredEvent

                    self._persist_events.append(
                        KvStoredEvent(block_hashes=resident, tier=TIER_PERSIST))

        def make_cache():
            return model.init_kv_cache(
                config.num_blocks, config.block_size, cache_dtype,
                **({"slots": config.max_batch_size} if self._recurrent
                   else {}))

        self._cache_specs = None
        if mesh is None:
            cache = make_cache()
        else:
            from dynamo_tpu.models.quant import align_specs, prune_specs

            params = jax.device_put(
                params,
                jax.tree.map(
                    lambda s: jax.sharding.NamedSharding(mesh, s),
                    align_specs(params, prune_specs(
                        params, model.partition_specs(), mesh)),
                    is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
                ),
            )
            # cache sharding pruned the same way (a kv-head axis the mesh
            # doesn't divide replicates rather than failing device_put)
            self._cache_specs = prune_specs(
                jax.eval_shape(make_cache),
                model.cache_spec(quant=self.cache_quant), mesh
            )
            # made in its shards: the whole cache of a sharded model need
            # not fit one device (Mistral-7B at tp 4: 16 GiB, 4 a device)
            # (jitted once, here at init: the jit is what shards the zeros)
            cache = jax.jit(  # dt: noqa[DT101]
                make_cache, out_shardings=self._cache_sharding())()
        self.params = params
        self.cache = cache
        # what this engine counts (obs/metric_names.py ENGINE_COUNTS says
        # what each is): metrics() reads it, /metrics the sum over the
        # process's engines.  Retired at close(), or when collected unclosed
        self.counts = counts = EngineCounts()
        self._retire_counts = track_engine(self, counts)
        # what this engine is spread over
        counts.mesh_tp = 1 if mesh is None else mesh.shape.get(AXIS_MODEL, 1)
        counts.mesh_devices = 1 if mesh is None else mesh.size
        # what the cache is made of: its layers (a looped decoder keeps one
        # per pass of every layer) and what one token costs across them all
        counts.cache_layers = int(jax.tree.leaves(self._pool())[0].shape[0])
        counts.kv_bytes_per_token = (
            self.kv_bytes_per_block() // config.block_size)
        # ... and what a slot's recurrent state costs, whatever its length
        if self._recurrent:
            counts.state_layers = int(cache["state"].shape[0])
            counts.state_bytes_per_slot = model.state_bytes_per_slot()
            # ... and whether the decode program updates it in one kernel
            counts.state_update_kernel = int(
                model.state_update_impl()[0] == "pallas")
        counts.prefix_reuse = int(self.prefix_reuse)
        # ... and which layers read a window of the context only
        counts.window_layers = int(getattr(model.config, "window_layers", 0))
        counts.sliding_window = int(
            getattr(model.config, "sliding_window", None) or 0)
        # whether the model's forward sizes something of its own by a
        # prefill's ``prefix_blocks``; where it only hands the value to the
        # attention call, that call's dispatch rule says whether the value
        # may key a program (``_prefix_blocks``)
        self._prefix_sizes_forward = bool(
            getattr(model, "prefix_blocks_sizes_forward", True))
        # the totals a model keeps on the device, in the order of the columns
        # of its cache's ``moe_counts`` (read back with each dispatch)
        self._device_count_keys = getattr(model, "moe_count_keys", ())

        # where a dispatch's small operands go (``_upload_dispatch``):
        # replicated over the mesh, the layout the jitted serving calls are
        # compiled for, so that a call re-lays nothing out; None with no
        # mesh (the default device, uncommitted)
        self._operand_sharding = None if mesh is None else (
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))

        # the engine's keys: ``jax.random.split``'s chain from the seed, a
        # key a dispatch, drawn ``KEY_BLOCK`` at a time into ``_keys``,
        # which lives where the operands go; a dispatch's buffer says which
        # of them is its own (``_next_key``)
        self._key_block = jax.jit(
            key_block, static_argnames="length",
            out_shardings=self._operand_sharding)
        self._rng, self._keys = self._key_block(jax.device_put(
            jax.random.PRNGKey(config.seed), self._operand_sharding),
            length=KEY_BLOCK)
        self._key_at = 0

        def under_mesh(impl):
            """``impl`` traced with the engine's mesh in scope: the
            attention dispatch (ops/paged_attention.py) reads the
            tensor-parallel axis from it and runs its Pallas kernels per
            kv-head shard under shard_map."""
            if mesh is None:
                return impl

            @functools.wraps(impl)
            def traced(*args, **kwargs):
                with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                    return impl(*args, **kwargs)

            return traced

        # the jitted entry points: each impl behind the one transfer of its
        # operands (``packed``): ``(params, cache, keys, bufs, layout=,
        # ...)`` -> the impl's results
        self._step_fn = jax.jit(
            under_mesh(packed(self._step_impl)), donate_argnums=(1,),
            static_argnames=("layout", "prefix_blocks", "k_cand", "exact"),
        )
        self._multi_fn = jax.jit(
            under_mesh(packed(self._multi_impl)), donate_argnums=(1,),
            static_argnames=("layout", "k_cand", "exact"),
        )
        self._spec_fn = jax.jit(
            under_mesh(packed(self._spec_impl)), donate_argnums=(1,),
            static_argnames=("layout", "k_cand", "exact"),
        )
        self._ragged_fn = jax.jit(
            under_mesh(packed(self._ragged_impl)), donate_argnums=(1,),
            static_argnames=("layout", "prefix_blocks", "k_cand", "exact"),
        )
        # the fifth donated serving impl: unified mixed prefill+decode
        # dispatch (decode rows + prefill spans on one flat token axis)
        self._unified_fn = jax.jit(
            under_mesh(packed(self._unified_impl)), donate_argnums=(1,),
            static_argnames=("layout", "row_tokens", "prefix_blocks",
                             "k_cand", "exact"),
        )
        # the three a prefill chunk goes out through: their jit caches are
        # the prefill programs this engine has built (``_count_prefill``)
        self._prefill_fns = (self._step_fn, self._ragged_fn, self._unified_fn)
        # sequence-parallel long-prefill (ring attention over the "data"
        # axis): one dispatch computes the whole prompt with the sequence
        # sharded across the mesh — SURVEY §5 long-context path
        self._sp_size = 0
        if (
            mesh is not None
            and config.sp_prefill_threshold > 0
            and AXIS_DATA in mesh.axis_names
            and mesh.shape[AXIS_DATA] > 1
        ):
            if not hasattr(model, "forward_seq_parallel") or not getattr(
                    model, "supports_seq_parallel", True):
                # fail at construction, not mid-serving on the first long
                # prompt (Llama-family and absorbed-MLA DeepSeek have the
                # ring path; expanded-MLA and future families without one
                # land here — supports_seq_parallel lets a model veto SP
                # for specific configs even though the method exists)
                raise ValueError(
                    f"{type(model).__name__} does not support seq-parallel "
                    "prefill (this config); disable sp_prefill_threshold"
                )
            self._sp_size = mesh.shape[AXIS_DATA]
            self._sp_fn = jax.jit(
                packed(self._sp_impl),
                static_argnames=("layout", "nb", "k_cand", "exact"),
            )

        self.slots: list[Optional[EngineRequest]] = [None] * config.max_batch_size
        self.waiting: "queue.SimpleQueue[EngineRequest]" = queue.SimpleQueue()
        self._admitted: list[EngineRequest] = []  # waiting for a slot/blocks
        self._by_id: dict[str, EngineRequest] = {}
        self._abort_q: "queue.SimpleQueue[str]" = queue.SimpleQueue()
        # aborts that arrived before their request was even admitted
        self._pending_aborts: set[str] = set()
        self._lock = threading.Lock()
        # ops enqueued by other threads, run on the engine thread at the next
        # step boundary (KV scatter/gather, remote-prefill completion, ...)
        self._ops: "queue.SimpleQueue[tuple[Callable, concurrent.futures.Future]]" = (
            queue.SimpleQueue()
        )
        # prefill-side held blocks: finished remote-decode prefills whose
        # blocks must survive until the transfer out completes
        self._held: dict[str, list[int]] = {}
        # streamed-handoff commit hooks (llm/kv/stream.py): per request,
        # fn(committed_block_ids, done) fired on the engine thread at each
        # chunk boundary (jitted scan bodies preclude per-layer callbacks —
        # chunk granularity is the documented fallback, docs/kv_streaming.md)
        # and once more with done=True when the prefill completes
        self._commit_hooks: dict[str, Callable[[list[int], bool], None]] = {}
        # steps of the loop by kind, and prefill work actually computed
        # (dedupe- and cancel-aware): read by tests, benchmarks and
        # cellbench, on no metrics surface
        self.steps = 0
        self.prefill_steps = 0
        self.decode_steps = 0
        self.sp_prefills = 0             # seq-parallel long-prefill dispatches
        self.prompt_tokens_computed = 0
        self._index_topk = int(getattr(model.config, "index_topk", 0) or 0)
        # (sequences, tokens, table blocks, block size, prefix blocks) ->
        # whether a prefill chunk of that shape attends in masked form, where
        # the model has two forms of attention over a selection
        self._attends_masked = getattr(model, "attends_masked", None)
        # (block tables, lengths, block size) -> cached rows the model's
        # decode attention fetches a layer, where the model can say
        self._decode_rows_fetched = getattr(model, "decode_rows_fetched", None)
        # the same -> index keys the model's decode indexer fetches a layer
        self._index_keys_read = getattr(model, "index_keys_read", None)
        # passes of the layer stack a token runs: ut_steps for a looped decoder
        self._ut_steps = int(getattr(model.config, "ut_steps", 1) or 1)
        self._decode_tiling = self._flash_decode_tiling()
        # one clock read a finished dispatch, stamped on every output its
        # host work emits (LLMEngineOutput.emitted_at)
        self._emit_at = 0.0
        # called, if set, where a batch of emits is complete (the end of a
        # dispatch's host work, of a step, of fail_all): a front door whose
        # ``emit`` only collects hands the batch over there in one hop
        # (AsyncLLMEngine); a plain ``emit`` callable needs none
        self.flush_outputs: Optional[Callable[[], None]] = None
        # cached _unified_penalties host buffers (invalidated on
        # admission/finish; incremental append between turns)
        self._pen_cache: Optional[dict] = None
        self._last_was_prefill = False
        self._admit_seq = 0              # order of admission: prefill is served in it
        # dispatch-ahead (``_settle``): at most one dispatch un-read-back
        self._inflight: Optional[_Inflight] = None
        # what a decode dispatch carries when there is nothing to carry:
        # made once, so every decode call has the same operands
        self._no_carry = self._carry_operand(
            np.zeros((1, config.max_batch_size), np.int32))

    # ----------------------------------------------------------- step kernel
    def _step_impl(self, params, cache, *args, prefix_blocks=None,
                   k_cand=K_MAX, exact=False, grammar=None, jrows=None,
                   jstate=None, jdepth=None, jstack=None, min_p=None,
                   bias_tokens=None, bias_vals=None, seeds=None,
                   seed_rows=None, seq_slots=None):
        return unified_step(self.model, params, cache, *args,
                            prefix_blocks=prefix_blocks, k_cand=k_cand,
                            exact=exact, grammar=grammar, jrows=jrows,
                            jstate=jstate, jdepth=jdepth, jstack=jstack,
                            min_p=min_p, bias_tokens=bias_tokens,
                            bias_vals=bias_vals, seeds=seeds,
                            seed_rows=seed_rows, seq_slots=seq_slots)

    def _ragged_impl(self, params, cache, tokens, positions, block_tables,
                     seq_lens, slot_idx, seq_ids, seq_starts, row_offsets,
                     last_idx, rng, temp, top_k, top_p, *, prefix_blocks=0,
                     k_cand=K_MAX, exact=False, grammar=None, jrows=None,
                     jstate=None, jdepth=None, jstack=None, min_p=None,
                     bias_tokens=None, bias_vals=None, seeds=None,
                     seed_rows=None):
        return ragged_prefill_step(
            self.model, params, cache, tokens, positions, block_tables,
            seq_lens, slot_idx, seq_ids, seq_starts, row_offsets, last_idx,
            rng, temp, top_k, top_p, prefix_blocks=prefix_blocks,
            k_cand=k_cand, exact=exact, grammar=grammar, jrows=jrows,
            jstate=jstate, jdepth=jdepth, jstack=jstack, min_p=min_p,
            bias_tokens=bias_tokens, bias_vals=bias_vals, seeds=seeds,
            seed_rows=seed_rows)

    def _unified_impl(self, params, cache, tokens, positions, block_tables,
                      seq_lens, slot_idx, seq_ids, seq_starts, row_offsets,
                      last_idx, rng, temp, top_k, top_p, *, row_tokens=0,
                      prefix_blocks=0, k_cand=K_MAX, exact=False,
                      grammar=None, jrows=None, jstate=None, jdepth=None,
                      jstack=None, min_p=None, bias_tokens=None,
                      bias_vals=None, seeds=None, seed_rows=None,
                      pen_tokens=None, pen_first=None, freq_pen=None,
                      pres_pen=None):
        return unified_token_step(
            self.model, params, cache, tokens, positions, block_tables,
            seq_lens, slot_idx, seq_ids, seq_starts, row_offsets, last_idx,
            rng, temp, top_k, top_p, pen_tokens, pen_first, freq_pen,
            pres_pen, row_tokens=row_tokens, prefix_blocks=prefix_blocks,
            k_cand=k_cand, exact=exact, grammar=grammar, jrows=jrows,
            jstate=jstate, jdepth=jdepth, jstack=jstack, min_p=min_p,
            bias_tokens=bias_tokens, bias_vals=bias_vals, seeds=seeds,
            seed_rows=seed_rows)

    def _sp_impl(self, params, tokens, positions, last_idx, rng, temp,
                 top_k, top_p, *, nb, k_cand=K_MAX, exact=False):
        """Sequence-parallel prefill: ring attention over mesh["data"],
        then sample the first token and lay the fresh KV out as cache
        blocks [L, nb, 2, Bs, HkD] (sharded like the pool, so the
        follow-up scatter is a resident-layout write).  With the int8
        cache the blocks are quantized here, in the same dispatch."""
        hidden, kv = self.model.forward_seq_parallel(
            params, tokens, positions, self.mesh, sp_axis=AXIS_DATA
        )
        last_h = hidden[jnp.arange(1), last_idx]
        logits = self.model.compute_logits(params, last_h)
        out = sample_full(logits, rng, temp, top_k, top_p,
                          k_cand=k_cand, exact=exact)
        l, _, b, s, hkd = kv.shape
        bs = self.config.block_size
        blocks = kv[:, :, 0].reshape(l, 2, nb, bs, hkd).transpose(0, 2, 1, 3, 4)
        if self.cache_quant:
            from dynamo_tpu.ops.kv_quant import (
                QuantKvCache, pad_scales, quantize_kv_rows,
            )

            hk = self.model.config.num_kv_heads
            q8, sc = quantize_kv_rows(
                blocks.reshape(l, nb, 2, bs, hk, hkd // hk)
            )  # int8 [..., Bs, Hk, D], scale f32 [..., Bs, Hk]
            blocks = QuantKvCache(
                q8.reshape(l, nb, 2, bs, hkd),
                # token-minor [L, nb, 2, Hk, Bs] -> tile-padded [.., Hp, Sp]
                pad_scales(jnp.swapaxes(sc, -1, -2)),
            )
        blocks = jax.lax.with_sharding_constraint(
            blocks, self._cache_sharding()
        )
        return out, blocks

    def _spec_impl(self, params, cache, tokens, positions, block_tables,
                   seq_lens, slot_idx, rng, temperature, top_k, top_p,
                   min_p, seeds, seed_rows, *, k_cand=K_MAX, exact=False):
        """Speculative verify: forward S tokens per row against the paged
        cache (KV scattered like prefill) and SAMPLE at every position
        with that position's own noise — the host accepts the proposal
        prefix the samples agree with.

        This is exact rejection sampling for the n-gram proposer: the
        proposal is a point mass, so "sample from the target and accept
        iff it matches" accepts with probability p(x) — the canonical
        min(1, p/q) rule — and on mismatch the drawn sample is already
        distributed as the renormalised residual (p restricted to ≠ x).
        Every emitted token is therefore distributed exactly as plain
        decoding, at any temperature.  Greedy rows (temp 0) reduce to
        argmax.  Seeded rows reuse the (seed, position, token-id) noise
        of engine/sampling.py, so their streams are bit-identical with
        speculation on or off (tests/test_spec_decode.py)."""
        hidden, cache = self.model.forward(
            params, tokens, positions, cache, block_tables, seq_lens, slot_idx
        )
        logits = self.model.compute_logits(params, hidden)  # [B, S, V]
        b, s, v = logits.shape
        rep = lambda a: jnp.repeat(a, s)
        sampled, _, _, _ = sample_full(
            logits.reshape(b * s, v), rng,
            rep(temperature), rep(top_k), rep(top_p),
            min_p=rep(min_p), seeds=rep(seeds), seed_rows=rep(seed_rows),
            # fold index = the sampled token's absolute sequence position,
            # matching unified_step/multi_decode_step exactly
            seed_steps=positions.reshape(b * s) + 1,
            # the caller threads _sampling_mode's (k_cand, exact) through,
            # so the verify candidate policy matches what the plain decode
            # path would use for the same batch (seeds force exact there)
            k_cand=k_cand, exact=exact,
        )
        return sampled.reshape(b, s).astype(jnp.int32), cache

    def _multi_impl(self, params, cache, *args, k_cand=K_MAX, exact=False,
                    grammar=None, jrows=None, jstate=None, jdepth=None,
                    jstack=None,
                    min_p=None, bias_tokens=None, bias_vals=None,
                    seeds=None, seed_rows=None, carry_tokens=None,
                    carry_rows=None):
        return multi_decode_step(
            self.model, params, cache, *args,
            grammar=grammar, jrows=jrows, jstate=jstate, jdepth=jdepth,
            jstack=jstack, min_p=min_p, bias_tokens=bias_tokens,
            bias_vals=bias_vals, seeds=seeds, seed_rows=seed_rows,
            carry_tokens=carry_tokens, carry_rows=carry_rows,
            block_size=self.config.block_size,
            k_cand=k_cand, exact=exact,
        )

    def _cache_sharding(self):
        """NamedSharding tree matching the cache pytree (bf16 array or
        QuantKvCache data+scale pair), mesh-pruned at init."""
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            self._cache_specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )

    def _flash_decode_tiling(self) -> Optional[tuple[int, int]]:
        """(G, C) of the flash-decode kernel at this engine's geometry (a
        shard's heads under a mesh); None for a model with attention
        kernels of its own."""
        from dynamo_tpu.ops.pallas.registry import decode_tiling

        if hasattr(self.model, "attention_impls"):
            return None
        mc, tp = self.model.config, self.counts.mesh_tp
        q_bytes = jnp.dtype(mc.jax_dtype).itemsize
        return decode_tiling(
            max(1, mc.num_heads // tp),
            max(1, mc.num_kv_heads * mc.head_dim // tp),
            self.config.block_size, 1 if self.cache_quant else q_bytes,
            q_bytes)

    def _count_decode_blocks(self, seq_lens: np.ndarray, s_q: int = 1) -> None:
        """Blocks the rows of a decode dispatch own, and what the kernel's
        groups of G slots (in slot order, G shrunk by the queries a row as
        the kernel shrinks it) fetched when every slot went up to its
        group's longest row in chunks of C blocks."""
        from dynamo_tpu.ops.pallas.registry import decode_group_and_chunk

        if self._decode_tiling is None:
            return
        g, c = decode_group_and_chunk(
            len(seq_lens), s_q, self.config.max_blocks_per_seq,
            *self._decode_tiling)
        bs = self.config.block_size
        blocks = -(-seq_lens // bs)
        walked = int(blocks.sum())
        bound = int((-(-blocks.reshape(-1, g).max(axis=1) // c)).sum()) * g * c
        self.counts.decode_kv_blocks_walked_total += walked
        self.counts.decode_kv_blocks_group_bound_total += bound
        layers = self.counts.window_layers
        if layers:
            # a window layer's walk begins at the block that holds the
            # first query's oldest visible key (position context - s_q + 1
            # - window: ops/pallas/decode_attention.py)
            first = np.maximum(
                seq_lens - s_q + 1 - self.counts.sliding_window, 0) // bs
            self.counts.decode_kv_window_blocks_walked_total += layers * int(
                (blocks - np.minimum(first, blocks)).sum())
            self.counts.decode_kv_window_blocks_span_total += layers * walked

    def _prefix_blocks(self, phase: str, blocks: int, span: int) -> int:
        """The static ``prefix_blocks`` of a prefill dispatch ("prefill":
        one request's chunk; "ragged": a flat token axis) of ``span``
        tokens whose longest cached prefix is ``blocks`` blocks: rounded up
        to a power of two, so that the programs stay O(log) where the value
        sizes a gather, and one value for every prefix where the dispatch
        rule says nothing reads it (``ops/paged_attention.py::
        prefill_program_key``: the flash kernel streams the prefix by its
        true length, which the operands carry)."""
        pb = 0 if blocks == 0 else min(
            1 << (blocks - 1).bit_length(), self.config.max_blocks_per_seq)
        if self._prefix_sizes_forward:
            return pb
        mc = self.model.config
        return prefill_program_key(
            phase, pb, span, getattr(mc, "sliding_window", None),
            num_kv_heads=mc.num_kv_heads, block_size=self.config.block_size,
            quant=self.cache_quant, tp=self.counts.mesh_tp)

    def attention_impls(self) -> dict[str, tuple[str, str]]:
        """phase -> ("pallas" | "xla", why), as the dispatch in
        ops/paged_attention.py decides it for this engine's geometry and
        mesh (the same static rule, asked up front for the start-up
        line).  ``windowed`` is the worst case over a request's life; the
        line then names the layers that have the window."""
        from dynamo_tpu.ops.paged_attention import (
            ATTENTION_PHASES,
            attention_impl,
        )

        if hasattr(self.model, "attention_impls"):
            # a model with attention kernels of its own names them
            return self.model.attention_impls()
        window = getattr(self.model.config, "sliding_window", None)
        windowed = window is not None and self.config.max_model_len > window
        impls = {
            phase: attention_impl(
                phase, num_kv_heads=self.model.config.num_kv_heads,
                block_size=self.config.block_size, quant=self.cache_quant,
                windowed=windowed, tp=self.counts.mesh_tp)
            for phase in ATTENTION_PHASES
        }
        if windowed:    # which layers: "6 window layers of 1,024, 2 full"
            n = self.counts.window_layers
            kinds = (f"{n} window layers of {window:,}, "
                     f"{self.model.config.num_layers - n} full")
            impls = {phase: (impl, f"{why}; {kinds}")
                     for phase, (impl, why) in impls.items()}
        return impls

    # ------------------------------------------------------- JSON grammar
    def attach_grammar_tokenizer(self, tokenizer, eos_ids=None) -> None:
        """Provide the tokenizer JSON-mode tables are compiled from; the
        compile itself runs lazily on the first json_mode request."""
        if self._grammar is None:
            self._grammar_tok = (tokenizer, tuple(eos_ids or self.eos_token_ids))

    def _ensure_grammar(self) -> Optional[JsonGrammar]:
        if self._grammar is None and self._grammar_tok is not None:
            tok, eos = self._grammar_tok
            self._grammar_tok = None
            self._grammar = JsonGrammar.from_tokenizer(tok, eos_ids=eos)
            log.info("compiled JSON grammar tables (%d states x %d tokens)",
                     self._grammar.tables.n_states,
                     self._grammar.tables.vocab_size)
        return self._grammar

    def _grammar_usable(self) -> bool:
        g = self._ensure_grammar()
        return g is not None and any(
            0 <= e < self.model.config.vocab_size for e in g.tables.eos_ids
        )

    @staticmethod
    def _grammar_key(req: EngineRequest):
        """None | "json" | ("choice", ...) | ("regex", ...) — which
        grammar (if any) constrains this request.  guided_regex wins over
        json_mode: schema requests carry both, regex enforcing the shape
        and json_mode serving as the uncompilable-regex fallback."""
        # regex before json: schema requests carry BOTH (the regex enforces
        # the schema's shape; json_mode is the documented fallback if that
        # regex turns out uncompilable)
        if req.sampling.guided_regex:
            return ("regex", req.sampling.guided_regex)
        if req.sampling.json_mode:
            return "json"
        if req.sampling.guided_choice:
            return ("choice",) + tuple(req.sampling.guided_choice)
        return None

    # composite state budget: a dispatch's composed tables must stay well
    # inside int16 ids; requests that would exceed it wait for slots to
    # free (same backpressure shape as NoFreeBlocks)
    GRAMMAR_STATE_BUDGET = 16384

    def _grammar_states_bound(self, key) -> int:
        """Upper bound on a grammar's state count.  Regex grammars compile
        (and cache) their tables here — the DFA size is not knowable from
        the pattern text, and admission must reject/stall BEFORE a
        dispatch composes an overflowing table."""
        if key == "json":
            return 128  # the JSON pushdown automaton is ~90 states
        if key[0] == "regex":
            return self._tables_for(key).n_states
        return sum(len(c.encode("utf-8")) for c in key[1:]) + 2

    def _active_grammar_budget_ok(self, new_key) -> bool:
        keys = {self._grammar_key(r) for r in self.slots if r is not None}
        keys.discard(None)
        keys.add(new_key)
        return (sum(self._grammar_states_bound(k) for k in keys)
                <= self.GRAMMAR_STATE_BUDGET)

    def _tables_for(self, key):
        """Host VocabTables for one grammar key (request-relative state
        space).  Choice tables compile on first use and cache by choices."""
        if key == "json":
            return self._grammar.tables
        if key in self._choice_tables:
            cached = self._choice_tables[key]
            if isinstance(cached, Exception):
                raise cached  # known-bad pattern: re-raise, don't recompile
            return cached
        try:
            if key[0] == "regex":
                tables = compile_regex_vocab(
                    self._grammar.token_bytes, key[1],
                    eos_ids=self._grammar.tables.eos_ids,
                )
            else:
                tables = compile_choice_vocab(
                    self._grammar.token_bytes, list(key[1:]),
                    eos_ids=self._grammar.tables.eos_ids,
                )
        except Exception as e:
            # cache the failure (bounded): a resubmitted bad pattern must
            # not pay the compile cost again, and varied bad patterns must
            # not grow the cache without limit or starve live tables
            failures = [k for k, v in self._choice_tables.items()
                        if isinstance(v, Exception)]
            if len(failures) >= 32:
                self._choice_tables.pop(failures[0])
            self._choice_tables[key] = e
            raise
        cap = max(16, self.config.max_batch_size)
        if len(self._choice_tables) >= cap:
            # evict a set no active request is using — in-flight grammars
            # must stay resident or every dispatch would recompile them
            active = {self._grammar_key(r) for r in self.slots
                      if r is not None}
            victim = next(
                (k for k, v in self._choice_tables.items()
                 if k not in active and not isinstance(v, Exception)),
                None,
            )
            if victim is not None:
                self._choice_tables.pop(victim)
                self._gdev_cache.clear()  # composites may reference it
        self._choice_tables[key] = tables
        return tables

    def _composite_for(self, keys: tuple):
        """(device tables, {key: state offset}) for a dispatch whose
        constrained rows use exactly ``keys`` (json first — the pushdown
        sentinel resolves against offset-0 ids)."""
        if keys not in self._gdev_cache:
            comp, offs = compose_tables([self._tables_for(k) for k in keys])
            # pad the state axis to a power of two: the table rides the
            # jitted step as a pytree, so each distinct shape is a fresh
            # executable — bucketing keeps the count O(log) over keysets
            n = comp.n_states
            pad = (1 << max(0, (n - 1).bit_length())) - n
            if pad:
                comp = dataclasses.replace(
                    comp,
                    next_state=np.pad(comp.next_state, ((0, pad), (0, 0))),
                    npops=np.pad(comp.npops, ((0, pad), (0, 0))),
                    popbits=np.pad(comp.popbits, ((0, pad), (0, 0))),
                    npush=np.pad(comp.npush, ((0, pad), (0, 0))),
                    eos_ok=np.pad(comp.eos_ok, (0, pad)),
                    terminal_only=np.pad(comp.terminal_only, (0, pad)),
                )
            if len(self._gdev_cache) >= 8:
                self._gdev_cache.clear()
            self._gdev_cache[keys] = (
                jax.device_put(
                    device_tables(comp, self.model.config.vocab_size),
                    self._operand_sharding),
                dict(zip(keys, offs)),
            )
        return self._gdev_cache[keys]

    def _sampling_extras(self, reqs, rows=None, b=None) -> dict:
        """min_p / logit_bias device kwargs for one dispatch, or {} when no
        request uses them (the common case compiles no extra executables).

        ``rows``: slot index per request for batch-shaped dispatches
        (decode); None = requests are the dispatch rows in order (prefill).
        ``b`` overrides the dispatch row count (ragged prefill: the padded
        sequence-row axis, not max_batch_size).
        """
        kw = {}
        if b is None:
            b = self.config.max_batch_size if rows is not None else len(reqs)
        at = (lambda i: rows[i]) if rows is not None else (lambda i: i)
        if any(r.sampling.min_p > 0 for r in reqs):
            mp = np.zeros(b, np.float32)
            for i, r in enumerate(reqs):
                mp[at(i)] = r.sampling.min_p
            kw["min_p"] = mp
        if any(r.sampling.seed is not None and not r.sampling.greedy
               for r in reqs):
            sd = np.zeros(b, np.int32)
            sr = np.zeros(b, bool)
            for i, r in enumerate(reqs):
                if r.sampling.seed is not None and not r.sampling.greedy:
                    sd[at(i)] = int(r.sampling.seed) & 0x7FFFFFFF
                    sr[at(i)] = True
            kw["seeds"] = sd
            kw["seed_rows"] = sr
        if any(r.sampling.logit_bias for r in reqs):
            longest = max(len(r.sampling.logit_bias or {}) for r in reqs)
            nb = max(8, 1 << (longest - 1).bit_length())  # pow2 buckets
            toks = np.full((b, nb), -1, np.int32)
            vals = np.zeros((b, nb), np.float32)
            for i, r in enumerate(reqs):
                for j, (t, v) in enumerate(
                    list((r.sampling.logit_bias or {}).items())[:nb]
                ):
                    toks[at(i), j] = int(t)
                    vals[at(i), j] = float(v)
            kw["bias_tokens"] = toks
            kw["bias_vals"] = vals
        return kw  # host arrays: the dispatch sites batch-upload them

    def _dispatch_keys(self, reqs) -> tuple:
        """Ordered grammar keys for one dispatch: json first (pushdown
        sentinel constraint), then choice sets in first-seen order."""
        keys = {self._grammar_key(r) for r in reqs}
        keys.discard(None)
        # canonical order: identical grammar sets must hit the same cached
        # composite regardless of request arrival order
        return tuple(sorted(keys, key=lambda k: (k != "json", k)))

    def _gram_kwargs(self, gram) -> dict:
        """Device kwargs for one dispatch's grammar state, or {}."""
        if gram is None:
            return {}
        keys, jrows, jstate, jdepth, jstack = gram
        gdev, _ = self._composite_for(keys)
        # row-state arrays stay host-side here; the dispatch sites fold
        # them into their single batched device_put
        return dict(
            grammar=gdev,
            jrows=np.asarray(jrows), jstate=np.asarray(jstate),
            jdepth=np.asarray(jdepth), jstack=np.asarray(jstack),
        )

    def _sampling_mode(self, reqs) -> tuple[int, bool]:
        """(k_cand, exact) for this dispatch: exact full top-k whenever a
        request asks for top_k beyond the approx candidate set, so large
        top_k never silently truncates.  k_cand is power-of-two bucketed
        (executable count stays O(log)) and capped at 1024 — the deep tail
        beyond that carries negligible probability mass."""
        want = max((r.sampling.top_k for r in reqs), default=0)
        exact = bool(self.config.exact_sampling)
        if any(r.sampling.seed is not None and not r.sampling.greedy
               for r in reqs):
            # seeded determinism requires the exact sorted candidate set:
            # the true top-K_MAX is then batch-composition-independent
            exact = True
        k_cand = K_MAX
        if want > K_MAX:
            k_cand = min(1 << (want - 1).bit_length(), 1024)
            exact = True
        return k_cand, exact

    def _next_key(self):
        """Where in ``self._keys`` the next dispatch's key lies: the chain
        moves one split a dispatch, whatever the dispatch is."""
        if self._key_at == len(self._keys):
            self._rng, self._keys = self._key_block(
                self._rng, length=len(self._keys))
            self._key_at = 0
        at = self._key_at
        self._key_at += 1
        return np.asarray(at, np.int32)

    def _upload_dispatch(self, host_args, gkw=None):
        """ONE host->device transfer a device for a dispatch's small
        operands, positional AND grammar/extras rows: what the host pays
        for a put does not depend on its size, a put per array was most of
        a launch (on one chip 3.5-4.8 ms of a turn for nine arrays; under a
        mesh each array is a transfer *a device*, and an uncommitted one is
        re-laid out inside the jitted call on pjit's slow path).  The
        arrays travel as one int32 buffer (``operands.pack``), put once,
        from the host, to where the serving program wants them (replicated
        over the mesh, or the default device); the program takes the
        buffer apart itself (``packed``).  ``None`` among ``host_args``
        marks where the impl takes the dispatch's key; the buffer's first
        word says where in ``self._keys`` that is.

        Returns (the buffers, their static layout, gkw without its host
        arrays: what is left lives on the device already)."""
        host_kw, device_kw = {}, {}
        for k, v in (gkw or {}).items():
            (host_kw if isinstance(v, np.ndarray) else device_kw)[k] = v
        bufs, layout = operands.pack((
            self._next_key(),
            tuple(a if a is None else np.asarray(a) for a in host_args),
            host_kw))
        bufs = jax.device_put(bufs, self._operand_sharding)
        self.counts.operand_buffers_total += (
            len(bufs) * self.counts.mesh_devices)
        return bufs, layout, device_kw

    def _run_step(self, tokens, positions, block_tables, seq_lens, slot_idx,
                  last_idx, temp, top_k, top_p, prefix_blocks=None,
                  k_cand=K_MAX, exact=False, gram=None, extras=None,
                  reqs=(), carried=None):
        """Upload and issue one prefill dispatch (``unified_step``) for
        ``reqs``; returns its outputs (sampled [B], logprob [B], cand_ids
        [B,C], cand_lps [B,C]) **still on the device**.  Nothing is read
        back here: the caller hands them to :meth:`_settle`, which finishes
        the dispatch issued before this one first."""
        gkw = self._gram_kwargs(gram)
        gkw.update(extras or {})
        step_timeline.enter("upload", carried=carried)
        bufs, layout, gkw = self._upload_dispatch(
            (tokens, positions, block_tables, seq_lens, slot_idx, last_idx,
             None, temp, top_k, top_p), gkw)
        step_timeline.enter("dispatch", kind="step")
        self._note_issue(reqs)
        statics = dict(layout=layout, prefix_blocks=prefix_blocks,
                       k_cand=k_cand, exact=exact)
        if perf_model.wants("step"):
            perf_model.offer(
                "step", self._step_fn,
                (self.params, self.cache, self._keys, bufs), kw=gkw,
                statics=statics)
        out, self.cache = self._step_fn(
            self.params, self.cache, self._keys, bufs, **statics, **gkw)
        self.steps += 1
        return out

    def _run_multi_decode_step(self, tokens, positions, block_tables, seq_lens,
                               limits, temp, top_k, top_p, pen=None, gram=None,
                               extras=None, k_cand=K_MAX,
                               exact=False, *, carry_rows, carried=None):
        """Upload and issue one decode; returns (sampled [1,B], logprob
        [1,B], cand_ids [1,B,C], cand_lps [1,B,C]) still on the device.
        Rows marked in ``carry_rows`` start from the sample of the decode
        in flight (``multi_decode_step``)."""
        host = [tokens, positions, block_tables, seq_lens, limits, None,
                temp, top_k, top_p, *(pen or ())]
        gkw = self._gram_kwargs(gram)
        gkw.update(extras or {})
        gkw["carry_rows"] = carry_rows
        gkw["carry_tokens"] = (
            self._carry_operand(self._inflight.out[0]) if carry_rows.any()
            else self._no_carry)
        step_timeline.enter("upload", carried=carried)
        bufs, layout, gkw = self._upload_dispatch(host, gkw)
        step_timeline.enter("dispatch", kind="decode_multi")
        statics = dict(layout=layout, k_cand=k_cand, exact=exact)
        if perf_model.wants("decode_multi"):
            perf_model.offer(
                "decode_multi", self._multi_fn,
                (self.params, self.cache, self._keys, bufs), kw=gkw,
                statics=statics)
        out, self.cache = self._multi_fn(
            self.params, self.cache, self._keys, bufs, **statics, **gkw)
        self.steps += 1
        return out

    # ------------------------------------------- what a dispatch carried
    def _carried(self, rows: int, tokens: int, seq_lens) -> dict:
        """For the profiler's dyn.upload / dispatch / readback events of
        the dispatch being built: its rows, its tokens (prompt tokens of a
        prefill, rows of a decode) and the sum of its rows' context
        lengths.  Built only while a profiler session is open."""
        if not step_timeline.profiling():
            return _NOT_PROFILED
        return {"rows": rows, "tokens": tokens, "ctx": int(seq_lens.sum())}

    def _note_issue(self, reqs) -> None:
        """A prefill dispatch carrying ``reqs`` has just been issued (call
        right after ``enter("dispatch")``): for those it is the first to
        carry, the end of their turn wait."""
        now = 0.0
        for req in reqs:
            req.prefill_chunks += 1
            if not req.first_issue_at:
                now = now or time.perf_counter()
                req.first_issue_at = now
                req.first_issue_step = step_timeline.busy_steps_total

    def _host_post(self) -> None:
        """A dispatch has been read back: its host work starts, and every
        output that work emits carries this one clock read."""
        step_timeline.enter("host_post")
        self._emit_at = time.perf_counter()

    # ------------------------------------------------------- dispatch-ahead
    def _carry_operand(self, arr):
        """``arr`` placed as a decode's ``sampled`` output is handed to
        the next decode: replicated over the mesh, so that the operand has
        one layout whether it is a real carry or ``_no_carry``."""
        if self.mesh is None and isinstance(arr, jax.Array):
            return arr
        return jax.device_put(arr, self._operand_sharding)

    def _may_stay_in_flight(self, rec: _Inflight) -> bool:
        """THE rule of dispatch-ahead, asked once a dispatch is issued:
        may the turn end with it un-read, so that the next one is built,
        uploaded and issued while the device runs it?  Yes when the next
        dispatch can need nothing of it that only the host could compute:
        a decode of plain sampling rows (its sample is carried on the
        device, lengths are predictable), or a prefill that the
        alternation follows with a decode (rows are running, chunking is
        on): that decode holds none of the prefill's rows, and the first
        token is read right behind it.  With no row running the prefill is
        read back at once, as ever: nothing is there to issue behind it.
        The engine-wide paths that build from the host's tokens every turn
        (unified dispatch, speculation) never leave one: they are
        alternatives to this overlap, and keep the serial step."""
        if (not rec.deferrable or self._unified_enabled()
                or self.config.spec_tokens > 0):
            return False
        return rec.kind == "decode_multi" or self._decode_follows()

    def _decode_follows(self) -> bool:
        """With a prefill just issued: is the next dispatch a decode?"""
        return bool(self.config.prefill_chunk_tokens) and any(
            r is not None and r.state is RequestState.RUNNING
            for r in self.slots)

    def _issue_behind(self, reqs, carry_ok: bool = False
                      ) -> Optional[_Inflight]:
        """Called before a decode over ``reqs`` is built.  If it can go
        behind the dispatch in flight, return that one; else read that one
        back first (a pipeline drain) and return None.  It can when no
        request is in both (a prefill in flight holds no running row), or
        when the one in flight is a decode (of plain rows, or it would not
        have stayed: its sample is carried over on the device) and the
        new one is such a decode too (``carry_ok``) — else the rows' tokens,
        grammar states or penalty buffers are the host's to compute from
        the readback.  A prefill is never built with a prefill in flight
        (``_schedule`` reads it back first)."""
        fl = self._inflight
        if fl is None:
            return None
        if any(fl.rows.get(r.slot) is r for r in reqs) and not (
                carry_ok and fl.kind == "decode_multi"):
            self._drain_pipeline()
            return None
        return fl

    def _settle(self, rec: _Inflight) -> None:
        """A dispatch has just been issued: now finish the one issued
        before it (readback, appends, stop checks, commits, emits — host
        work the device no longer waits for), then ``rec`` itself unless
        it may stay in flight for the next turn."""
        prev, self._inflight = self._inflight, rec
        # under a mesh the array's own is_ready() covers its shards
        step_timeline.in_flight(rec.out[0].is_ready)
        if isinstance(self.cache, dict) and "moe_counts" in self.cache:
            rec.counted = expert_totals(self.cache["moe_counts"])
        if prev is not None:
            if rec.kind == "decode_multi":
                # counted like decode_dispatches_total, at the dispatch:
                # their ratio is how often a decode hid its round trip
                self.counts.ahead_dispatches_total += 1
            self._finish_dispatch(prev)
        if not self._may_stay_in_flight(rec):
            self._inflight = None
            self._finish_dispatch(rec)

    def _finish_dispatch(self, rec: _Inflight) -> None:
        """Read ``rec`` back and do its host work.  Requests that ended
        while it was in flight gave up nothing yet: the program wrote one
        position past their stop into blocks they still owned (never
        committed); slot and blocks go back now that it has run."""
        step_timeline.enter("readback", kind=rec.kind, issued=False,
                            carried=rec.carried)
        # ONE batched transfer: per-array np.asarray would issue a
        # device->host round trip per output (per-array latency is the
        # cost that matters on a remote-attached chip)
        out, counted = jax.device_get((tuple(rec.out), rec.counted))
        self.counts.device_gets_total += 1
        if counted is not None:
            # totals since the cache was made, so set and not added
            for key, total in zip(self._device_count_keys, counted):
                setattr(self.counts, key, int(total))
        self._host_post()
        rec.finish(out)
        for req in rec.ended:
            self._release_slot(req)
        # here and not only at the step's end: _settle may read two
        # dispatches back in one step, and the first one's outputs must not
        # wait for the second one's device_get
        self._outputs_ready()

    def _outputs_ready(self) -> None:
        if self.flush_outputs is not None:
            self.flush_outputs()

    def _drain_pipeline(self) -> None:
        """Finish the dispatch in flight, if any, before going on: what
        comes next needs the host's view of it (see ``_issue_behind``), or
        there is nothing to issue behind it."""
        rec, self._inflight = self._inflight, None
        if rec is not None:
            self.counts.pipeline_drains_total += 1
            self._finish_dispatch(rec)

    # ------------------------------------------------------- cross-thread API
    def submit(self, request: EngineRequest) -> None:
        request.submitted_at = time.perf_counter()
        self.waiting.put(request)

    def abort(self, request_id: str) -> None:
        self._abort_q.put(request_id)

    def run_on_step(self, fn: Callable) -> "concurrent.futures.Future":
        """Enqueue ``fn`` to run on the engine thread at the next step
        boundary; the returned future resolves with its result.  This is the
        only safe way for other threads to touch the cache / block manager
        (single-writer discipline, SURVEY.md §5 race detection)."""
        fut: concurrent.futures.Future = concurrent.futures.Future()
        self._ops.put((fn, fut))
        return fut

    def has_work(self) -> bool:
        return (
            not self.waiting.empty()
            or bool(self._admitted)
            or not self._ops.empty()
            or any(s is not None for s in self.slots)
            or self._inflight is not None
        )

    def fail_all(self) -> None:
        """Fail every in-flight and queued request (engine step blew up) so
        callers get an error finish instead of a hung stream.  A dispatch
        in flight is dropped unread: its samples may be the failed step's."""
        fl, self._inflight = self._inflight, None
        step_timeline.in_flight(None)
        for req in (fl.ended if fl is not None else ()):
            self._release_slot(req)   # ended already: no second finish
        for req in [r for r in self.slots if r is not None]:
            self._finish_slot(req, FinishReason.ERROR)
        for req in self._admitted:
            self._finish(req, FinishReason.ERROR)
        self._admitted.clear()
        while True:
            try:
                self._finish(self.waiting.get_nowait(), FinishReason.ERROR)
            except queue.Empty:
                break
        self._outputs_ready()

    def _count_prefill(self, rows: int, tokens: int, budget: int = 0) -> None:
        """One prefill dispatch: ``rows`` sequences packed, ``tokens`` prompt
        tokens computed, under a token budget of ``budget`` (0 for a
        one-request or seq-parallel dispatch: those offer none)."""
        c = self.counts
        c.prefill_dispatches_total += 1
        c.prefill_rows_dispatched += rows
        c.prefill_tokens_total += tokens
        if budget > 0:
            c.prefill_budget_offered += budget
            c.prefill_budget_used += tokens
        c.prefill_programs_total = sum(
            fn._cache_size() for fn in self._prefill_fns)
        self._count_tokens(tokens)

    def _count_tokens(self, tokens: int) -> None:
        """``tokens`` went out in a dispatch: each runs the layer stack
        ``ut_steps`` times (passes / tokens = cellbench's
        loop.passes_per_token)."""
        self.counts.loop_tokens_total += tokens
        self.counts.loop_passes_total += tokens * self._ut_steps

    def metrics(self) -> dict:
        """ForwardPassMetrics equivalent (ref kv_router/protocols.rs:30-47)."""
        active = sum(1 for s in self.slots if s is not None)
        out = {
            "request_active_slots": active,
            "request_total_slots": self.config.max_batch_size,
            "kv_active_blocks": self.block_manager.active_blocks,
            "kv_total_blocks": self.block_manager.num_blocks,
            "num_requests_waiting": self.waiting.qsize() + len(self._admitted),
            "kv_usage_perc": self.block_manager.usage,
        }
        out.update((e.key, e.value(self.counts))
                   for e in ENGINE_COUNTS if e.key)
        if self.host_pool is not None:
            out.update(self.host_pool.stats())
        if self.persist_store is not None:
            out.update(self.persist_store.stats())
        # step-timeline headline (process-global; obs/timeline.py)
        out["host_gap_ms_per_turn"] = step_timeline.host_gap_ms_per_turn
        return out

    # -------------------------------------------------------------- main loop
    def step(self) -> bool:
        """Run one scheduling iteration.  Returns False when idle.

        **A step issues dispatch N+1 and then finishes dispatch N**
        (dispatch-ahead, docs/engine_scheduling.md).  At most one dispatch
        is un-read-back (``_inflight``), and never a decode ahead of a
        ready prefill: the turn admits, picks its dispatch by the
        alternation below, builds it from state the host can predict
        (lengths, not tokens), uploads and issues it behind the one in
        flight, and only then reads that one back and does its host work
        (appends, stop checks, block commits, emits) — while the device
        already runs N+1.  What may go ahead is decided per turn from the
        rows at hand (``_issue_behind``, ``_may_stay_in_flight``);
        everything else reads back first and runs as the serial step,
        which is the same code with nothing in flight.  A stop the host
        cannot foresee (EOS, a stop token) is found one dispatch late: the
        row's ahead-sample is thrown away (``ahead_discards_total``).
        Idle is idle: with nothing to issue the dispatch in flight is read
        back and the next call returns False.

        The body is wrapped in the dtspan step timeline (obs/timeline.py):
        ``begin()`` (first thing in ``_step_inner``) opens the step and its
        first phase, the scheduler and every dispatch helper ``enter()``
        the phase that starts, ``end()`` closes the last — so per-phase
        wall time sums to step wall time by construction, and under a
        profiler session each phase is one
        ``dyn.<phase>`` event on this thread."""
        try:
            return self._step_inner()
        finally:
            # what an abort, a rejected admission or a failed step emitted
            # outside any dispatch's host work
            self._outputs_ready()
            step_timeline.end()

    def _step_inner(self) -> bool:
        # Opened here, not in step(): a span that is open when this frame
        # starts and closes inside it hides the whole step from a reader
        # that rebuilds the host's call tree by time (cellbench's
        # breakdown.idle_gaps under the profiler's Python tracer).
        step_timeline.begin()  # opens kv_spill_restore
        if self._inflight is not None and (
                self._pending_offload or not self._ops.empty()
                or not self._abort_q.empty()):
            # evicted blocks to snapshot, an operation of another thread
            # on the cache / block manager (KV scatter and gather,
            # remote-prefill completion), an abort: they see a quiescent
            # engine, and a cancelled row is not issued once more
            self._drain_pipeline()
        self._drain_offload()  # evictions from the previous step's tail
        step_timeline.enter("host_ops")
        self._process_ops()
        self._process_aborts()
        step_timeline.enter("admission")
        self._admit()
        step_timeline.enter("host_build")
        fl = self._inflight
        worked = self._schedule()
        if fl is not None and self._inflight is fl:
            # nothing was issued behind it: the last tokens of an engine
            # going quiet are emitted now, not at the next arrival
            self._drain_pipeline()
            return True
        return worked

    def _schedule(self) -> bool:
        """Choose this turn's dispatch and run it; False with none."""
        fl = self._inflight
        if (fl is not None and fl.kind != "decode_multi"
                and not (self._last_was_prefill and self._decode_follows())):
            # a prefill stays in flight only for the decode the alternation
            # issues behind it; the rows that were to decode are gone (cut
            # short, cancelled), so it is read back before this turn looks
            # at what is ready: its requests are about to change state
            self._drain_pipeline()
        # slots not yet decoding (waiting on external KV, or mid-chunked-
        # prefill): honour aborts here — _append_token never runs for them,
        # so without this a cancelled long prompt would keep prefilling
        for req in self.slots:
            if (
                req is not None
                and req.state in (RequestState.REMOTE_PREFILL, RequestState.PREFILL)
                and req.abort_requested
            ):
                self._finish_slot(req, FinishReason.CANCELLED)
        # in order of admission, not of slot: a new request takes the
        # lowest free slot, and served by slot it would cut in ahead of a
        # long prompt mid-prefill in a higher one, chunk after chunk (at a
        # full batch of 64 the 95th percentile of TTFT was 7x the median,
        # and timing chose which request starved: PERF.md, PR 27)
        ready = sorted(
            (
                r
                for r in self.slots
                if r is not None
                and r.state is RequestState.PREFILL
                and self._prefill_ready(r)
            ),
            key=lambda r: r.admit_seq,
        )
        decoding = any(
            r is not None and r.state is RequestState.RUNNING for r in self.slots
        )
        if self._unified_enabled():
            # unified token-budget scheduler: a mixed turn is ONE ragged
            # dispatch (decode rows + prefill spans on one flat axis) —
            # no alternation state machine, no per-switch round-trip
            return self._step_unified(ready, decoding)
        # chunked-prefill interleave: when both phases have work, alternate
        # one prefill turn (one chunk, or one ragged token-budget batch)
        # with one decode step so admissions never stall the decoders for
        # a whole long prompt (VERDICT r1 weak #2)
        if ready and decoding and self.config.prefill_chunk_tokens:
            if self._last_was_prefill:
                self._last_was_prefill = False
                self._run_decode()
            else:
                self._last_was_prefill = True
                self._dispatch_prefill(ready)
            return True
        if ready:
            self._last_was_prefill = True
            self._dispatch_prefill(ready)
            return True
        if decoding:
            self._last_was_prefill = False
            self._run_decode()
            return True
        return False

    def _unified_enabled(self) -> bool:
        return (
            self.config.unified_token_dispatch
            and self.config.prefill_token_budget > 0
            and getattr(self.model, "supports_unified_dispatch", False)
        )

    def _step_unified(self, ready: list[EngineRequest], decoding: bool
                      ) -> bool:
        """One turn of the unified token-budget scheduler: mixed work
        runs as ONE dispatch via :meth:`_run_unified`; pure-prefill turns
        keep the ragged token-budget batch and pure-decode turns keep the
        decode step (the speculative path only makes sense with no prefill
        sharing the axis)."""
        if ready and self._sp_eligible(ready[0]):
            # seq-parallel long prompts keep their dedicated dispatch
            self._count_ready(ready)
            self._run_sp_prefill(ready[0])
            return True
        ready = [r for r in ready if not self._sp_eligible(r)]
        if ready and decoding and self._run_unified(ready):
            self._count_ready(ready)
            return True
        if ready:
            self._dispatch_prefill(ready)
            return True
        if decoding:
            self._run_decode()
            return True
        return False

    def _process_ops(self) -> None:
        while True:
            try:
                fn, fut = self._ops.get_nowait()
            except queue.Empty:
                break
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except Exception as e:
                fut.set_exception(e)

    def _process_aborts(self) -> None:
        while True:
            try:
                rid = self._abort_q.get_nowait()
            except queue.Empty:
                break
            req = self._by_id.get(rid)
            if req is not None:
                req.abort_requested = True
                continue
            admitted = next(
                (r for r in self._admitted if r.request_id == rid), None
            )
            if admitted is not None:
                admitted.abort_requested = True
                continue
            # not seen yet: the request may still be in the cross-thread
            # waiting queue — remember the abort so admission applies it
            # (without this, cancelling a QUEUED request was silently lost
            # and it ran to completion)
            self._pending_aborts.add(rid)

    def _admit(self) -> None:
        # drain the cross-thread queue
        while True:
            try:
                req = self.waiting.get_nowait()
            except queue.Empty:
                break
            if req.request_id in self._pending_aborts:
                self._pending_aborts.discard(req.request_id)
                req.abort_requested = True
            self._admitted.append(req)
        # pending aborts unmatched after a full queue drain can never match:
        # a caller that submitted before aborting had its request visible in
        # this drain (_process_aborts runs before _admit each step), so the
        # leftovers are finished/unknown ids — drop them or the set grows
        # forever on abort-vs-finish races
        self._pending_aborts.clear()
        for req in list(self._admitted):
            if req.abort_requested:
                self._admitted.remove(req)
                self._finish(req, FinishReason.CANCELLED)
                continue
            slot = next((i for i, s in enumerate(self.slots) if s is None), None)
            if slot is None:
                break
            if req.prompt_len == 0:
                self._admitted.remove(req)
                self._finish(req, FinishReason.ERROR)
                continue
            if req.prompt_len >= self.config.max_model_len:
                self._admitted.remove(req)
                self._finish(req, FinishReason.LENGTH)
                continue
            gkey = self._grammar_key(req)
            if gkey is not None and not (
                self._grammar_usable()
                and (gkey == "json" or self._grammar.token_bytes is not None)
            ):
                # constrained decoding needs tokenizer-compiled tables AND
                # a model-vocab EOS id (terminal states are eos-only;
                # without one the mask would go all -inf on completion and
                # sampling degrades to uniform noise)
                self._admitted.remove(req)
                self._finish(req, FinishReason.ERROR)
                continue
            if gkey is not None:
                try:
                    budget_ok = self._active_grammar_budget_ok(gkey)
                except Exception:
                    if gkey[0] == "regex" and req.sampling.json_mode:
                        # schema-derived regex overflowed the DFA cap:
                        # fall back to the generic JSON grammar (prompt
                        # injection still steers the shape)
                        log.warning(
                            "schema regex uncompilable for %s; falling "
                            "back to generic JSON mode", req.request_id,
                        )
                        req.sampling.guided_regex = None
                        gkey = "json"
                        budget_ok = self._active_grammar_budget_ok(gkey)
                    else:
                        # bad pattern / oversized DFA with no fallback:
                        # fail the request, don't crash the engine step
                        log.exception("grammar compile failed for %s",
                                      req.request_id)
                        self._admitted.remove(req)
                        self._finish(req, FinishReason.ERROR)
                        continue
                if not budget_ok:
                    # composed dispatch tables must stay inside int16 state
                    # ids: wait for constrained slots to free
                    # (NoFreeBlocks-style backpressure, not an error)
                    break
            if req.seq is None:
                # built once: a request that NoFreeBlocks sends round again
                # keeps its chain, and one that starts as an earlier prompt
                # did takes that prompt's blocks from the memo
                req.seq, reused = self._chain_memo.sequence(
                    req.prompt, self.config.block_size)
                self.counts.prompt_blocks_admitted_total += len(req.seq.blocks)
                self.counts.prompt_blocks_reused_total += reused
            try:
                alloc = self.block_manager.allocate(
                    req.seq.sequence_hashes(), req.prompt_len
                )
            except NoFreeBlocks:
                break  # retry next step once blocks free up
            req.block_ids = alloc.block_ids
            req.cached_tokens = alloc.cached_tokens
            if self.host_pool is not None and alloc.joined_tokens == 0:
                # allocation may have evicted registered blocks — capture
                # their content BEFORE restore writes into the same ids.
                # (With joined in-flight blocks, restore would scatter host
                # content into blocks the owner is writing — skip; the
                # owner's compute is arriving anyway.)
                self._drain_pipeline()  # restore commits what it scatters
                self._drain_offload()
                self._restore_from_host(req)
            req.computed_tokens = req.cached_tokens
            req.wait_upto = req.cached_tokens + alloc.joined_tokens
            self._reserve_own(req)
            req.slot = slot
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
            if req.submitted_at:
                req.admitted_at = time.perf_counter()
                req.queue_wait_s = req.admitted_at - req.submitted_at
            req.state = (
                RequestState.REMOTE_PREFILL if req.remote_prefill else RequestState.PREFILL
            )
            self.slots[slot] = req
            self._by_id[req.request_id] = req
            self._admitted.remove(req)
            self._pen_cache = None  # live request set changed
            if req.on_allocated is not None:
                try:
                    req.on_allocated(req)
                except Exception:
                    # a dying caller (closed event loop) must not take down
                    # every other request via step() -> fail_all()
                    log.exception("on_allocated callback failed for %s", req.request_id)
                    req.abort_requested = True

    def _dispatch_prefill(self, ready: list[EngineRequest]) -> None:
        """One prefill turn over the READY requests (admission order): the
        head request keeps its historical routing (seq-parallel long
        prompts dispatch alone), otherwise the token-budget ragged batch
        packs every non-SP ready request — or, with batching disabled
        (prefill_token_budget=0) or a model without the ragged attention
        path, the legacy one-request dispatch."""
        self._count_ready(ready)
        head = ready[0]
        if self._sp_eligible(head):
            self._run_sp_prefill(head)
            return
        if self.config.prefill_token_budget > 0 and getattr(
            self.model, "supports_ragged_prefill", False
        ):
            self._run_prefill_batch(
                [r for r in ready if not self._sp_eligible(r)]
            )
        else:
            self._run_prefill(head)

    def _count_ready(self, ready: list[EngineRequest]) -> None:
        """A prefill dispatch goes out with ``ready`` standing ready for
        one: over the dispatches, the backlog a served request stood in
        (1.0 = nobody ever waited behind another's chunk)."""
        self.counts.prefill_ready_rows_total += len(ready)

    # ---------------------------------------------------------------- prefill
    def _reserve_own(self, req: EngineRequest) -> None:
        """Register this request as the computer of its not-yet-covered
        full prompt blocks, so concurrent identical prompts join these
        blocks instead of prefilling duplicates."""
        bs = self.config.block_size
        for i in range(req.wait_upto // bs, req.prompt_len // bs):
            blk = req.seq.blocks[i]
            if self.block_manager.reserve(blk.sequence_hash, req.block_ids[i]):
                req.reserved_pairs.append((blk.sequence_hash, req.block_ids[i]))

    def _prefill_ready(self, req: EngineRequest) -> bool:
        """Absorb joined in-flight blocks their owner has committed; return
        True when this request can dispatch a prefill chunk now (nothing
        ahead of ``computed_tokens`` is still being written by someone
        else).  If the owner aborted before committing, take over the
        remaining prompt ourselves."""
        bs = self.config.block_size
        bm = self.block_manager
        while req.computed_tokens < req.wait_upto:
            i = req.computed_tokens // bs
            if bm.block_committed(req.block_ids[i]):
                req.computed_tokens += bs
                req.cached_tokens += bs  # someone else's compute — a hit
                continue
            blk = req.seq.blocks[i]
            if bm.is_reserved(blk.sequence_hash):
                return False  # owner still prefilling — wait, don't recompute
            # owner vanished without committing: take over from here
            req.wait_upto = req.computed_tokens
            self._reserve_own(req)
        return True

    def _run_prefill(self, req: EngineRequest) -> None:
        cfg = self.config
        remaining = req.prompt_len - req.computed_tokens
        # chunked prefill: bound the tokens computed this dispatch so decode
        # steps interleave (step() alternates); non-final chunks end on a
        # block boundary so the next chunk stays block-aligned
        chunk = cfg.prefill_chunk_tokens or remaining
        take = min(remaining, chunk)
        final = take == remaining
        s = cfg.bucket_for(take)
        m = cfg.max_blocks_per_seq
        end = req.computed_tokens + take

        tokens = np.zeros((1, s), np.int32)
        positions = np.zeros((1, s), np.int32)
        slot_idx = np.full((1, s), -1, np.int32)
        tokens[0, :take] = req.prompt[req.computed_tokens : end]
        pos = np.arange(req.computed_tokens, end, dtype=np.int32)
        positions[0, :take] = pos
        bt = np.zeros((1, m), np.int32)
        bt[0, : len(req.block_ids)] = req.block_ids
        slot_idx[0, :take] = (
            bt[0, pos // cfg.block_size] * cfg.block_size + pos % cfg.block_size
        )
        seq_lens = np.asarray([end], np.int32)
        last_idx = np.asarray([take - 1], np.int32)

        # prefill fast path: attention over this chunk and the cached-prefix
        # blocks alone, not the whole padded table
        pb = self._prefix_blocks(
            "prefill", req.computed_tokens // cfg.block_size, s)
        # the form the model's forward traces for this shape (one rule)
        masked = self._attends_masked is not None and self._attends_masked(
            1, s, m, cfg.block_size, pb)

        k_cand, exact = self._sampling_mode([req])
        gram = None
        # only the final chunk's sample is kept — masking earlier chunks
        # would just burn an extra executable per prefill bucket
        gkey = self._grammar_key(req)
        if final and gkey is not None and self._ensure_grammar() is not None:
            keys = self._dispatch_keys([req])
            off = self._composite_for(keys)[1][gkey]
            gs, gd, gk = req.gstate
            gram = (keys, np.asarray([True]),
                    np.asarray([gs + off if gs > 0 else gs], np.int32),
                    np.asarray([gd], np.int32), np.asarray([gk], np.int32))
        carried = self._carried(1, take, seq_lens)
        out = self._run_step(
            tokens, positions, bt, seq_lens, slot_idx, last_idx,
            np.asarray([req.sampling.temperature], np.float32),
            np.asarray([req.sampling.top_k], np.int32),
            np.asarray([req.sampling.top_p], np.float32),
            prefix_blocks=pb, k_cand=k_cand, exact=exact, gram=gram,
            extras={**(self._sampling_extras([req]) if final else {}),
                    **({"seq_slots": np.asarray([req.slot], np.int32)}
                       if self._recurrent else {})},
            reqs=(req,), carried=carried,
        )
        self.prefill_steps += 1
        self._count_prefill(rows=1, tokens=take)

        def finish(out):
            if req.state is not RequestState.PREFILL:
                return  # cancelled while the chunk was in flight
            self.prompt_tokens_computed += take
            if masked:
                self.counts.prefill_masked_tokens_total += take
            req.computed_tokens = end
            self._commit_prefill_blocks(req)
            if final:  # else more chunks to go; the sample is discarded
                self._complete_prefill(req, *out)

        self._settle(_Inflight("step", out, finish, {req.slot: req},
                               carried=carried))

    def _commit_prefill_blocks(self, req: EngineRequest) -> None:
        """Offer newly completed prompt blocks to the block manager.  The
        ``committed_upto`` watermark makes chunked prefill linear: each
        chunk commits only the blocks it completed — re-offering every
        earlier block per chunk (commit is idempotent but not free) made
        an L-block prompt pay O(L^2) commit calls across its chunks."""
        bs = self.config.block_size
        done = req.computed_tokens // bs
        for blk in req.seq.blocks[req.committed_upto // bs : done]:
            self.block_manager.commit(
                req.block_ids[blk.position], blk.sequence_hash,
                blk.parent_sequence_hash, list(blk.tokens),
            )
        req.committed_upto = done * bs
        self._fire_commit_hook(req, done=False)

    def _run_prefill_batch(self, reqs: list[EngineRequest]) -> None:
        """Token-budget ragged prefill: pack up to ``prefill_token_budget``
        tokens of pending prefill work (several requests' chunks) onto one
        flat token axis and run ONE ragged dispatch.

        Each selected chunk occupies a contiguous block-aligned span of
        the flat axis (padding slots are -1 / seq_id -1), so the
        block-granular cache write and the ragged attention masks hold by
        construction.  The axis is bucketed via ``config.bucket_for`` and
        the sequence-row axis is power-of-two padded — executables stay
        O(log^2).  Only final-chunk rows' samples are kept: those rows
        carry their request's grammar state, sampling extras and seeds;
        mid-chunk rows sample garbage that the host discards."""
        cfg = self.config
        bs = cfg.block_size
        budget = cfg.prefill_token_budget
        sel: list[tuple[EngineRequest, int, bool]] = []  # (req, take, final)
        used = 0
        for req in reqs:
            avail = budget - used
            if avail < bs:
                break
            remaining = req.prompt_len - req.computed_tokens
            chunk = cfg.prefill_chunk_tokens or remaining
            take = min(remaining, chunk, avail)
            if take < remaining:
                # non-final chunks end block-aligned so the resumed chunk
                # starts block-aligned (fast-path + packing requirement)
                take = take // bs * bs
                if take == 0:
                    break
            sel.append((req, take, take == remaining))
            used += -(-take // bs) * bs  # span = block-rounded take

        r_real = len(sel)
        r_pad = 1 << max(0, (r_real - 1).bit_length())
        t_pad = cfg.bucket_for(used)
        m = cfg.max_blocks_per_seq
        tokens = np.zeros((1, t_pad), np.int32)
        positions = np.zeros((1, t_pad), np.int32)
        slot_idx = np.full((1, t_pad), -1, np.int32)
        seq_ids = np.full((1, t_pad), -1, np.int32)
        bt = np.zeros((r_pad, m), np.int32)
        seq_lens = np.zeros(r_pad, np.int32)
        starts = np.zeros(r_pad, np.int32)
        roff = np.zeros(r_pad, np.int32)
        last_idx = np.zeros(r_pad, np.int32)
        temp = np.zeros(r_pad, np.float32)
        top_k = np.zeros(r_pad, np.int32)
        top_p = np.ones(r_pad, np.float32)
        off = 0
        max_pb = 0
        for r, (req, take, final) in enumerate(sel):
            begin = req.computed_tokens
            end = begin + take
            tokens[0, off:off + take] = req.prompt[begin:end]
            pos = np.arange(begin, end, dtype=np.int32)
            positions[0, off:off + take] = pos
            bt[r, : len(req.block_ids)] = req.block_ids
            slot_idx[0, off:off + take] = (
                bt[r, pos // bs] * bs + pos % bs
            )
            seq_ids[0, off:off + take] = r
            seq_lens[r] = end
            starts[r] = begin
            roff[r] = off
            last_idx[r] = off + take - 1
            temp[r] = req.sampling.temperature
            top_k[r] = req.sampling.top_k
            top_p[r] = req.sampling.top_p
            max_pb = max(max_pb, begin // bs)
            off += -(-take // bs) * bs
        # cached-prefix gather bound: max over rows, like the single-request
        # path (rows with shorter prefixes mask by start)
        pb = self._prefix_blocks("ragged", max_pb, t_pad)

        finals = [(r, req) for r, (req, _, fin) in enumerate(sel) if fin]
        final_reqs = [req for _, req in finals]
        k_cand, exact = self._sampling_mode(final_reqs)
        gram = None
        if final_reqs and any(
            self._grammar_key(rq) for rq in final_reqs
        ) and self._ensure_grammar() is not None:
            keys = self._dispatch_keys(final_reqs)
            offs = self._composite_for(keys)[1]
            jrows = np.zeros(r_pad, bool)
            jstate = np.full(r_pad, INIT_STATE, np.int32)
            jdepth = np.zeros(r_pad, np.int32)
            jstack = np.zeros(r_pad, np.int32)
            for r, rq in finals:
                key = self._grammar_key(rq)
                if key is None:
                    continue
                jrows[r] = True
                gs, gd, gk = rq.gstate
                jstate[r] = gs + offs[key] if gs > 0 else gs
                jdepth[r], jstack[r] = gd, gk
            gram = (keys, jrows, jstate, jdepth, jstack)
        extras = None
        if final_reqs:
            extras = self._sampling_extras(
                final_reqs, rows=[r for r, _ in finals], b=r_pad
            )

        gkw = self._gram_kwargs(gram)
        gkw.update(extras or {})
        take_sum = sum(take for _, take, _ in sel)
        carried = self._carried(r_real, take_sum, seq_lens)
        step_timeline.enter("upload", carried=carried)
        bufs, layout, gkw = self._upload_dispatch(
            (tokens, positions, bt, seq_lens, slot_idx, seq_ids, starts,
             roff, last_idx, None, temp, top_k, top_p), gkw)
        step_timeline.enter("dispatch", kind="prefill_ragged")
        self._note_issue(req for req, _, _ in sel)
        statics = dict(layout=layout, prefix_blocks=pb, k_cand=k_cand,
                       exact=exact)
        if perf_model.wants("prefill_ragged"):
            perf_model.offer(
                "prefill_ragged", self._ragged_fn,
                (self.params, self.cache, self._keys, bufs), kw=gkw,
                statics=statics)
        out, self.cache = self._ragged_fn(
            self.params, self.cache, self._keys, bufs, **statics, **gkw)
        self.steps += 1
        self.prefill_steps += 1
        self._count_prefill(rows=r_real, tokens=take_sum, budget=budget)

        def finish(out):
            for r, (req, take, final) in enumerate(sel):
                if req.state is not RequestState.PREFILL:
                    continue  # cancelled while the chunk was in flight
                self.prompt_tokens_computed += take
                req.computed_tokens += take
                self._commit_prefill_blocks(req)
                if final:
                    self._complete_prefill(req, *(a[r:r + 1] for a in out))

        self._settle(_Inflight(
            "prefill_ragged", out, finish,
            {req.slot: req for req, _, _ in sel}, carried=carried))

    def _complete_prefill(self, req, sampled, lps, cids, clps) -> None:
        """Shared tail of chunked and sequence-parallel prefill: state
        transition, remote-decode holdout, first-token emission."""
        # a COMPLETED prefill must not count against the next arrival: reset
        # the interleave so a fresh prompt's first chunk runs immediately
        # instead of behind a decode step.  Only when no OTHER prefill is
        # mid-flight — a queue of short prompts must still alternate with
        # decode steps, or running decoders starve through the whole queue.
        if not any(
            r is not None and r is not req and r.state is RequestState.PREFILL
            for r in self.slots
        ):
            self._last_was_prefill = False
        req.state = RequestState.RUNNING
        self.counts.prompt_tokens_admitted_total += req.prompt_len
        self.counts.prompt_tokens_cached_total += req.cached_tokens
        if req.remote_decode:
            # prefill-only request: emit the first sampled token, hold the
            # blocks for transfer-out, free the slot (ref prefill_worker.py:148
            # runs generate(max_tokens=1, is_remote_decode=True))
            self._held[req.request_id] = list(req.block_ids)
            # done=True covers ALL blocks, including the partial tail
            # block _commit_prefill_blocks never reaches (it commits only
            # FULL blocks) — the streamed handoff's final chunk rides here
            self._fire_commit_hook(req, done=True)
            self.slots[req.slot] = None
            self._by_id.pop(req.request_id, None)
            req.state = RequestState.FINISHED
            req.finish_reason = FinishReason.STOP
            self.counts.tokens_generated += 1
            req.emit(
                LLMEngineOutput(
                    token_ids=[int(sampled[0])],
                    finish_reason=FinishReason.STOP,
                    cached_tokens=req.cached_tokens,
                )
            )
            return
        self._append_token(req, int(sampled[0]), first=True,
                           logprob=float(lps[0]), cand=(cids[0], clps[0]))

    # ------------------------------------------- unified mixed dispatch
    def _run_unified(self, ready: list[EngineRequest]) -> bool:
        """ONE mixed dispatch for this turn: every RUNNING slot
        contributes a decode row (1 fresh token) on the leading
        row-scatter region of the flat axis, then the READY prefill
        chunks pack block-aligned spans into the remaining token budget.
        The legacy interleave's two dispatches per mixed turn (decode
        step + prefill turn, with a device round-trip between) collapse
        to one — chunked-prefill-under-decode co-scheduling falls out of
        the layout.  Returns False when no decode row is dispatchable
        or no prefill chunk fits (the caller falls back to a pure
        prefill/decode turn)."""
        cfg = self.config
        bs = cfg.block_size
        m = cfg.max_blocks_per_seq
        # decode region: a STATIC block-multiple of the flat axis (one
        # slot per batch slot), so the prefill spans after it stay
        # block-aligned for the block-granular write and the executable
        # count gains no new axis
        d_region = -(-cfg.max_batch_size // bs) * bs
        budget = max(bs, cfg.prefill_token_budget - d_region)
        budget = min(budget, cfg.max_model_len - d_region)
        if budget < bs:
            return False  # flat axis cannot fit a span past the region

        dec: list[EngineRequest] = []
        for req in self.slots:
            if req is None or req.state is not RequestState.RUNNING:
                continue
            if self._grow_blocks(req, 1) is None:
                continue  # no slot for even the current token: LENGTH
            dec.append(req)
        if not dec:
            return False

        # prefill packing under the remaining budget (same selection as
        # _run_prefill_batch)
        sel: list[tuple[EngineRequest, int, bool]] = []
        used = 0
        for req in ready:
            avail = budget - used
            if avail < bs:
                break
            remaining = req.prompt_len - req.computed_tokens
            chunk = cfg.prefill_chunk_tokens or remaining
            take = min(remaining, chunk, avail)
            if take < remaining:
                take = take // bs * bs  # resumed chunks stay block-aligned
                if take == 0:
                    break
            sel.append((req, take, take == remaining))
            used += -(-take // bs) * bs  # span = block-rounded take
        if not sel:
            return False

        n_dec = len(dec)
        r_real = n_dec + len(sel)
        r_pad = 1 << max(0, (r_real - 1).bit_length())
        t_pad = cfg.bucket_for(d_region + used)
        tokens = np.zeros((1, t_pad), np.int32)
        positions = np.zeros((1, t_pad), np.int32)
        slot_idx = np.full((1, t_pad), -1, np.int32)
        seq_ids = np.full((1, t_pad), -1, np.int32)
        bt = np.zeros((r_pad, m), np.int32)
        seq_lens = np.zeros(r_pad, np.int32)
        starts = np.zeros(r_pad, np.int32)
        roff = np.zeros(r_pad, np.int32)
        last_idx = np.zeros(r_pad, np.int32)
        temp = np.zeros(r_pad, np.float32)
        top_k = np.zeros(r_pad, np.int32)
        top_p = np.ones(r_pad, np.float32)
        max_pb = 0
        for r, req in enumerate(dec):
            p = req.seq.total_tokens - 1  # uncomputed tail position
            tokens[0, r] = req.seq.last_token
            positions[0, r] = p
            slot_idx[0, r] = req.block_ids[p // bs] * bs + p % bs
            seq_ids[0, r] = r
            bt[r, : len(req.block_ids)] = req.block_ids
            seq_lens[r] = p + 1
            starts[r] = p  # full cached prefix; need NOT be block-aligned
            roff[r] = r
            last_idx[r] = r
            temp[r] = req.sampling.temperature
            top_k[r] = req.sampling.top_k
            top_p[r] = req.sampling.top_p
            max_pb = max(max_pb, -(-p // bs))
        off = d_region
        for j, (req, take, _final) in enumerate(sel):
            r = n_dec + j
            begin = req.computed_tokens
            end = begin + take
            tokens[0, off:off + take] = req.prompt[begin:end]
            pos = np.arange(begin, end, dtype=np.int32)
            positions[0, off:off + take] = pos
            bt[r, : len(req.block_ids)] = req.block_ids
            slot_idx[0, off:off + take] = bt[r, pos // bs] * bs + pos % bs
            seq_ids[0, off:off + take] = r
            seq_lens[r] = end
            starts[r] = begin
            roff[r] = off
            last_idx[r] = off + take - 1
            temp[r] = req.sampling.temperature
            top_k[r] = req.sampling.top_k
            top_p[r] = req.sampling.top_p
            max_pb = max(max_pb, begin // bs)
            off += -(-take // bs) * bs
        pb = self._prefix_blocks("ragged", max_pb, t_pad)

        # sampling rows: every decode row plus final-chunk prefill rows
        # (mid-chunk rows' samples are discarded below)
        samp = list(enumerate(dec)) + [
            (n_dec + j, rq) for j, (rq, _, fin) in enumerate(sel) if fin
        ]
        samp_reqs = [rq for _, rq in samp]
        k_cand, exact = self._sampling_mode(samp_reqs)
        gram = None
        if any(self._grammar_key(rq) for rq in samp_reqs) \
                and self._ensure_grammar() is not None:
            keys = self._dispatch_keys(samp_reqs)
            offs = self._composite_for(keys)[1]
            jrows = np.zeros(r_pad, bool)
            jstate = np.full(r_pad, INIT_STATE, np.int32)
            jdepth = np.zeros(r_pad, np.int32)
            jstack = np.zeros(r_pad, np.int32)
            for r, rq in samp:
                key = self._grammar_key(rq)
                if key is None:
                    continue
                jrows[r] = True
                gs, gd, gk = rq.gstate
                jstate[r] = gs + offs[key] if gs > 0 else gs
                jdepth[r], jstack[r] = gd, gk
            gram = (keys, jrows, jstate, jdepth, jstack)
        extras = self._sampling_extras(
            samp_reqs, rows=[r for r, _ in samp], b=r_pad)
        extras.update(self._unified_penalties(samp, r_pad))

        # growth allocations above may have evicted registered blocks
        # that this very dispatch writes into — offload them first
        step_timeline.enter("kv_spill_restore")
        self._drain_offload()
        step_timeline.enter("host_build")
        gkw = self._gram_kwargs(gram)
        gkw.update(extras)
        take_sum = sum(take for _, take, _ in sel)
        step_timeline.enter("upload", carried=self._carried(
            r_real, n_dec + take_sum, seq_lens))
        bufs, layout, gkw = self._upload_dispatch(
            (tokens, positions, bt, seq_lens, slot_idx, seq_ids, starts,
             roff, last_idx, None, temp, top_k, top_p), gkw)
        step_timeline.enter("dispatch", kind="unified")
        self._note_issue(req for req, _, _ in sel)
        statics = dict(layout=layout, row_tokens=d_region, prefix_blocks=pb,
                       k_cand=k_cand, exact=exact)
        if perf_model.wants("unified"):
            perf_model.offer(
                "unified", self._unified_fn,
                (self.params, self.cache, self._keys, bufs), kw=gkw,
                statics=statics)
        out, self.cache = self._unified_fn(
            self.params, self.cache, self._keys, bufs, **statics, **gkw)
        step_timeline.enter("readback")
        sampled, lps, cids, clps = jax.device_get(out)  # one batched pull
        self.counts.device_gets_total += 1
        self._host_post()
        self.steps += 1
        self.prefill_steps += 1
        self.decode_steps += 1
        self.prompt_tokens_computed += take_sum
        self._count_prefill(rows=len(sel), tokens=take_sum, budget=budget)
        c = self.counts
        c.unified_dispatches_total += 1
        c.unified_decode_rows += n_dec
        c.unified_prefill_tokens += take_sum
        c.unified_budget_offered += cfg.prefill_token_budget
        c.unified_budget_used += n_dec + take_sum
        self._count_tokens(n_dec)

        for r, req in enumerate(dec):
            want_lp = req.sampling.logprobs or req.sampling.top_logprobs > 0
            self._append_token(
                req, int(sampled[r]),
                logprob=float(lps[r]) if want_lp else None,
                cand=(cids[r], clps[r]) if want_lp else None,
            )
        for j, (req, take, final) in enumerate(sel):
            r = n_dec + j
            req.computed_tokens += take
            self._commit_prefill_blocks(req)
            if final:
                self._complete_prefill(
                    req, sampled[r:r + 1], lps[r:r + 1],
                    cids[r:r + 1], clps[r:r + 1],
                )
        return True

    def _unified_penalties(self, samp, r_pad: int) -> dict:
        """Penalty buffers for one unified dispatch, keyed by DISPATCH
        row (cf. :meth:`_penalty_buffers`, which keys by slot): a
        [R_pad, T] generated-token buffer + first-occurrence mask +
        per-row strengths.  {} when no sampling row uses penalties, so
        the common case compiles no extra executables.

        The host build is cached on (rows, shapes, live request set +
        penalty strengths): while the plan is stable, only the tokens
        generated since the previous turn are appended into the cached
        buffers instead of rebuilding the whole [R, T] arrays.  The
        cache is invalidated on admission and finish (slot placement
        changes rows) and misses on any shape change."""
        users = [(r, rq) for r, rq in samp
                 if rq.sampling.frequency_penalty
                 or rq.sampling.presence_penalty]
        if not users:
            return {}
        longest = max(rq.seq.total_tokens - rq.prompt_len
                      for _, rq in users)
        t_cap = max(16, 1 << max(0, longest - 1).bit_length())
        t_cap = min(t_cap, max(
            16, 1 << (self.config.max_model_len - 1).bit_length()))
        key = (r_pad, t_cap, tuple(
            (rq.request_id, r, rq.sampling.frequency_penalty,
             rq.sampling.presence_penalty) for r, rq in users))
        pc = self._pen_cache
        if pc is not None and pc["key"] == key:
            ptoks, pfirst = pc["ptoks"], pc["pfirst"]
            for r, rq in users:
                gen = rq.seq.tokens[rq.prompt_len:]
                seen = pc["seen"][rq.request_id]
                n = min(len(gen), t_cap)
                for j in range(pc["count"][rq.request_id], n):
                    t = gen[j]
                    ptoks[r, j] = t
                    if t not in seen:
                        pfirst[r, j] = True
                        seen.add(t)
                pc["count"][rq.request_id] = n
            out = dict(pc["out"])
        else:
            ptoks = np.full((r_pad, t_cap), -1, np.int32)
            pfirst = np.zeros((r_pad, t_cap), bool)
            freq = np.zeros(r_pad, np.float32)
            pres = np.zeros(r_pad, np.float32)
            seen_map: dict[str, set] = {}
            count_map: dict[str, int] = {}
            for r, rq in users:
                gen = rq.seq.tokens[rq.prompt_len:]
                n = min(len(gen), t_cap)
                seen: set[int] = set()
                for j, t in enumerate(gen[:n]):
                    ptoks[r, j] = t
                    if t not in seen:
                        pfirst[r, j] = True
                        seen.add(t)
                freq[r] = rq.sampling.frequency_penalty
                pres[r] = rq.sampling.presence_penalty
                seen_map[rq.request_id] = seen
                count_map[rq.request_id] = n
            out = dict(pen_tokens=ptoks, pen_first=pfirst,
                       freq_pen=freq, pres_pen=pres)
            self._pen_cache = dict(key=key, out=dict(out), ptoks=ptoks,
                                   pfirst=pfirst, seen=seen_map,
                                   count=count_map)
        return out

    # ------------------------------------------------ seq-parallel prefill
    def _sp_eligible(self, req: EngineRequest) -> bool:
        return (
            self._sp_size > 0
            and req.computed_tokens == 0
            and req.prompt_len >= self.config.sp_prefill_threshold
            # the SP first-token sample path has no grammar/bias/min_p
            # hooks — those requests take the chunked prefill path, which
            # threads _sampling_extras into the final chunk's sampler
            and not req.sampling.json_mode
            and not req.sampling.guided_choice
            and not req.sampling.guided_regex
            and not req.sampling.logit_bias
            and not req.sampling.min_p
            # the SP first-token sampler has no per-request seed hook
            and not (req.sampling.seed is not None
                     and not req.sampling.greedy)
        )

    def _run_sp_prefill(self, req: EngineRequest) -> None:
        """Whole-prompt prefill in ONE dispatch with the sequence sharded
        over mesh["data"] (ring attention — ops/ring_attention.py): the
        long-context path where even a single prompt's activations/KV
        exceed one chip's comfort.  KV comes back already block-shaped and
        pool-sharded; a donated scatter drops it into the paged cache."""
        self._drain_pipeline()  # reads back in its own turn, as ever
        cfg = self.config
        bs = cfg.block_size
        unit = bs * self._sp_size
        # pow2 bucketing in units of (block_size × sp) keeps the executable
        # count O(log) while satisfying both divisibility constraints
        units = -(-req.prompt_len // unit)
        units = 1 << (units - 1).bit_length()
        s_pad = units * unit
        nb_pad = s_pad // bs

        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, : req.prompt_len] = req.prompt
        # padding keys get positions beyond every real query → causally
        # invisible; padding queries produce discarded (finite) rows
        positions = np.arange(s_pad, dtype=np.int32)[None, :]
        last_idx = np.asarray([req.prompt_len - 1], np.int32)
        k_cand, exact = self._sampling_mode([req])
        step_timeline.enter("upload", carried=self._carried(
            1, req.prompt_len, last_idx + 1))
        bufs, layout, _ = self._upload_dispatch((
            tokens, positions, last_idx, None,
            np.asarray([req.sampling.temperature], np.float32),
            np.asarray([req.sampling.top_k], np.int32),
            np.asarray([req.sampling.top_p], np.float32),
        ))
        step_timeline.enter("dispatch", kind="sp_prefill")
        self._note_issue((req,))
        statics = dict(layout=layout, nb=nb_pad, k_cand=k_cand, exact=exact)
        if perf_model.wants("sp_prefill"):
            perf_model.offer(
                "sp_prefill", self._sp_fn, (self.params, self._keys, bufs),
                statics=statics)
        (sampled, lps, cids, clps), blocks = self._sp_fn(
            self.params, self._keys, bufs, **statics)
        step_timeline.enter("readback")
        sampled, lps, cids, clps = jax.device_get(
            (sampled, lps, cids, clps))  # one batched transfer
        self.counts.device_gets_total += 1
        self._host_post()
        nb = -(-req.prompt_len // bs)
        self.cache = scatter_blocks_inplace(
            self.cache, req.block_ids[:nb],
            jax.tree.map(lambda a: a[:, :nb], blocks),
        )
        self.steps += 1
        self.prefill_steps += 1
        self.sp_prefills += 1
        self._count_prefill(rows=1, tokens=req.prompt_len)
        self.prompt_tokens_computed += req.prompt_len
        req.computed_tokens = req.prompt_len
        self._commit_prefill_blocks(req)
        self._complete_prefill(req, sampled, lps, cids, clps)

    # ----------------------------------------------------------------- decode
    # ----------------------------------------------------- speculative decode
    def _spec_eligible(self, reqs) -> bool:
        """Speculation composes with plain sampling (greedy, temperature,
        top_k <= K_MAX, top_p, min_p, per-request seeds — the verify pass
        samples each position with its own noise, see ``_spec_impl``).
        Still excluded: penalties (the verify forward doesn't thread the
        generated-token buffers through accepted positions), logprobs
        (not returned per verified position), logit_bias, and grammar
        modes (mask state advances once per emitted token on the decode
        path).  top_k > K_MAX needs the widened exact-candidate dispatch
        the verify executable doesn't compile."""
        return all(
            (r.sampling.greedy or r.sampling.top_k <= K_MAX)
            and not r.sampling.frequency_penalty
            and not r.sampling.presence_penalty
            and not r.sampling.logprobs
            and not r.sampling.top_logprobs
            and not r.sampling.logit_bias
            and not r.sampling.json_mode
            and not r.sampling.guided_choice
            and not r.sampling.guided_regex
            for r in reqs
        )

    def _grow_blocks(self, req: EngineRequest, extra_tokens: int,
                     ahead: int = 0) -> Optional[int]:
        """Extend ``req``'s block table to cover ``extra_tokens`` more
        positions beyond its uncomputed tail; returns the row's token
        limit, or None when not even the current token has a slot (the
        request was finished at LENGTH).  Shared by the decode, unified
        and speculative dispatch builders.  ``ahead`` = 1 for a row whose
        token of the decode in flight the host has not appended yet: its
        tail is one further, and with no slot for it the row is only left
        out (the turn that knows the token decides)."""
        cfg = self.config
        p = req.seq.total_tokens - 1 + ahead
        want_tokens = min(p + extra_tokens, cfg.max_model_len)
        needed = (want_tokens - 1) // cfg.block_size + 1
        if len(req.block_ids) < needed:
            try:
                req.block_ids.extend(
                    self.block_manager.allocate_raw(needed - len(req.block_ids))
                )
            except NoFreeBlocks:
                if len(req.block_ids) * cfg.block_size <= p:
                    if not ahead:
                        self._cut_short(req)
                    return None
        return min(len(req.block_ids) * cfg.block_size, cfg.max_model_len)

    def _try_spec_decode(self) -> bool:
        """Prompt-lookup speculative dispatch (engine/spec.py): verify up
        to spec_tokens proposed continuations per row in ONE forward and
        emit the matching prefix + one bonus token.  Returns False when no
        row has a proposal (caller falls back to the plain decode step).

        On TPU the verify forward takes the multi-query flash-decode
        kernel (ops/pallas/decode_attention.py) — only owned blocks
        stream from HBM.  The block table is additionally SLICED to the
        batch's live context (power-of-two bucketed, so executables stay
        O(log)), which is what bounds the pure-JAX fallback's gather."""
        from dynamo_tpu.engine.spec import propose_ngram

        cfg = self.config
        k = cfg.spec_tokens
        b, m = cfg.max_batch_size, cfg.max_blocks_per_seq
        s = k + 1
        active = [
            r for r in self.slots
            if r is not None and r.state is RequestState.RUNNING
        ]
        if not active or not self._spec_eligible(active):
            return False

        tokens = np.zeros((b, s), np.int32)
        positions = np.zeros((b, s), np.int32)
        slot_idx = np.full((b, s), -1, np.int32)
        bt = np.zeros((b, m), np.int32)
        seq_lens = np.zeros(b, np.int32)
        limits = np.zeros(b, np.int32)
        temp = np.zeros(b, np.float32)  # inactive rows: greedy, ignored
        top_k = np.zeros(b, np.int32)
        top_p = np.ones(b, np.float32)
        min_p = np.zeros(b, np.float32)
        seeds = np.zeros(b, np.int32)
        seed_rows = np.zeros(b, bool)
        props: dict[int, list[int]] = {}
        rows: list[EngineRequest] = []
        any_prop = False
        # draft-model proposals for the whole batch in one dispatch;
        # rows the draft can't serve fall back to n-gram lookup below
        draft_props: dict[int, list[int]] = {}
        if self.draft is not None:
            draft_props = self.draft.propose(active, k, m)
        for req in active:
            i = req.slot
            temp[i] = req.sampling.temperature
            top_k[i] = req.sampling.top_k
            top_p[i] = req.sampling.top_p
            min_p[i] = req.sampling.min_p
            if req.sampling.seed is not None and not req.sampling.greedy:
                seeds[i] = int(req.sampling.seed) & 0x7FFFFFFF
                seed_rows[i] = True
            p = req.seq.total_tokens - 1  # position of the uncomputed tail
            limit = self._grow_blocks(req, s)
            if limit is None:
                continue
            prop = draft_props.get(i) or propose_ngram(
                req.seq.tokens, cfg.spec_ngram, k
            )
            prop = prop[: max(0, limit - (p + 1))]  # KV positions stay in range
            props[i] = prop
            any_prop = any_prop or bool(prop)
            rows.append(req)
            row_tokens = [req.seq.last_token] + prop
            n = len(row_tokens)
            tokens[i, :n] = row_tokens
            positions[i, :n] = np.arange(p, p + n, dtype=np.int32)
            blk = positions[i, :n] // cfg.block_size
            slot_idx[i, :n] = (
                np.asarray(req.block_ids, np.int32)[blk] * cfg.block_size
                + positions[i, :n] % cfg.block_size
            )
            bt[i, : len(req.block_ids)] = req.block_ids
            seq_lens[i] = p + n
            limits[i] = limit
        if not any_prop or not rows:
            return False
        # slice the block table to the batch's live context, pow2-bucketed:
        # the verify gather then reads O(max context) KV, not O(model_len)
        blocks_used = max(1, -(-int(seq_lens.max()) // cfg.block_size))
        m_used = min(m, 1 << (blocks_used - 1).bit_length())

        step_timeline.enter("kv_spill_restore")
        self._drain_offload()
        step_timeline.enter("host_build")
        k_cand, exact = self._sampling_mode(rows)
        step_timeline.enter("upload", carried=self._carried(
            len(rows), len(rows) * s, seq_lens))
        bufs, layout, _ = self._upload_dispatch(
            (tokens, positions, bt[:, :m_used], seq_lens, slot_idx, None,
             temp, top_k, top_p, min_p, seeds, seed_rows))
        step_timeline.enter("dispatch", kind="spec_verify")
        statics = dict(layout=layout, k_cand=k_cand, exact=exact)
        if perf_model.wants("spec_verify"):
            perf_model.offer(
                "spec_verify", self._spec_fn,
                (self.params, self.cache, self._keys, bufs), statics=statics)
        verified, self.cache = self._spec_fn(
            self.params, self.cache, self._keys, bufs, **statics)
        step_timeline.enter("readback")
        verified = jax.device_get(verified)
        self.counts.device_gets_total += 1
        self._host_post()
        self.steps += 1
        self.decode_steps += 1
        self.counts.spec_steps += 1
        self.counts.decode_dispatches_total += 1
        self.counts.decode_rows_dispatched_total += len(rows)
        self._count_decode_blocks(seq_lens, tokens.shape[1])
        self._count_tokens(len(rows) * tokens.shape[1])
        for req in rows:
            i = req.slot
            prop = props.get(i, [])
            # accept the proposal prefix the verify samples agree with,
            # then the bonus token from the first disagreeing (or final)
            # position — each emitted token is that position's own sample
            a = 0
            while a < len(prop) and prop[a] == int(verified[i, a]):
                a += 1
            emit = [int(verified[i, j]) for j in range(a + 1)]
            self.counts.spec_proposed += len(prop)
            self.counts.spec_accepted += a
            allowed = min(len(emit), int(limits[i] - (req.seq.total_tokens - 1)))
            for t in emit[:allowed]:
                if req.state is not RequestState.RUNNING:
                    break  # EOS/stop/max_tokens mid-acceptance
                self._append_token(req, t)
            if req.state is RequestState.RUNNING and allowed < len(emit):
                self._cut_short(req)
        return True

    def _run_decode(self) -> None:
        """One decode dispatch: one token a running sequence.  A sequence
        with no block space for its current token is finished at LENGTH.

        This builds and issues the dispatch; its readback and host work
        are ``finish`` below, run by :meth:`_settle` — in this turn, or in
        the next one behind that turn's dispatch.  Issued behind a decode
        still in flight, a row of that decode takes its token on the
        device (``carry_rows``) and is built for length + 1; a row that
        ends in the one in flight by ``max_tokens`` or ``max_model_len``
        is left out."""
        cfg = self.config
        if cfg.spec_tokens > 0 and self._try_spec_decode():
            return
        b, m = cfg.max_batch_size, cfg.max_blocks_per_seq
        running = [r for r in self.slots
                   if r is not None and r.state is RequestState.RUNNING]
        # plain rows: what the device can carry from the decode in flight
        # (a grammar state or a penalty buffer is built by the host from
        # the token, so such a batch reads back first)
        plain = not any(
            self._grammar_key(r) or r.sampling.frequency_penalty
            or r.sampling.presence_penalty for r in running)
        fl = self._issue_behind(running, carry_ok=plain)
        if fl is None:
            # read back first: the rows that ended there are gone
            running = [r for r in running
                       if r.state is RequestState.RUNNING]
        tokens = np.zeros(b, np.int32)
        positions = np.zeros(b, np.int32)
        bt = np.zeros((b, m), np.int32)
        seq_lens = np.zeros(b, np.int32)
        limits = np.zeros(b, np.int32)
        temp = np.ones(b, np.float32)
        top_k = np.zeros(b, np.int32)
        top_p = np.ones(b, np.float32)
        carry_rows = np.zeros(b, bool)

        active: list[EngineRequest] = []
        for req in running:
            i = req.slot
            # a row of the decode in flight: its token is on the device,
            # its length is one more than the host has appended
            ahead = int(fl is not None and fl.rows.get(i) is req)
            total = req.seq.total_tokens + ahead
            if ahead:
                st = req.stops
                if (
                    req.abort_requested
                    or (st.max_tokens is not None
                        and req.generated + 1 >= st.max_tokens)
                    or total >= cfg.max_model_len
                ):
                    continue  # ends in the dispatch in flight: left out
            limit = self._grow_blocks(req, 1, ahead)
            if limit is None:
                continue  # not even the current token has a slot
            active.append(req)
            carry_rows[i] = bool(ahead)
            if not ahead:
                tokens[i] = req.seq.last_token
            positions[i] = total - 1  # the not-yet-computed last token
            bt[i, : len(req.block_ids)] = req.block_ids
            seq_lens[i] = total
            limits[i] = limit
            temp[i] = req.sampling.temperature
            top_k[i] = req.sampling.top_k
            top_p[i] = req.sampling.top_p

        if not active:
            return
        # growth allocations above may have evicted registered blocks that
        # this very dispatch writes into — offload them first
        step_timeline.enter("kv_spill_restore")
        self._drain_offload()
        step_timeline.enter("host_build")
        k_cand, exact = self._sampling_mode(active)
        carried = self._carried(len(active), len(active), seq_lens)
        pen = self._penalty_buffers(active)
        gram = None
        if any(self._grammar_key(r) for r in active) \
                and self._ensure_grammar() is not None:
            keys = self._dispatch_keys(active)
            offs = self._composite_for(keys)[1]
            jrows = np.zeros(b, bool)
            jstate = np.full(b, INIT_STATE, np.int32)
            jdepth = np.zeros(b, np.int32)
            jstack = np.zeros(b, np.int32)
            for r in active:
                k = self._grammar_key(r)
                if k is not None:
                    jrows[r.slot] = True
                    gs, gd, gk = r.gstate
                    # request-relative state id -> composite id
                    jstate[r.slot] = gs + offs[k] if gs > 0 else gs
                    jdepth[r.slot], jstack[r.slot] = gd, gk
            gram = (keys, jrows, jstate, jdepth, jstack)
        out = self._run_multi_decode_step(
            tokens, positions, bt, seq_lens, limits, temp, top_k, top_p,
            pen=pen, gram=gram,
            extras=self._sampling_extras(active, rows=[r.slot for r in active]),
            k_cand=k_cand, exact=exact,
            carry_rows=carry_rows, carried=carried,
        )  # [1, B], [1, B], [1, B, C], [1, B, C]
        self.counts.decode_dispatches_total += 1
        self.counts.decode_rows_dispatched_total += len(active)
        self._count_decode_blocks(seq_lens)
        self._count_tokens(len(active))
        if self._private_cache_layout:
            ctx = int(seq_lens.sum())
            picked = (int(np.minimum(seq_lens, self._index_topk).sum())
                      if self._index_topk else ctx)
            self.counts.attn_context_tokens_total += ctx
            self.counts.attn_selected_tokens_total += picked
            if self._decode_rows_fetched is not None:
                self.counts.attn_fetched_tokens_total += \
                    self._decode_rows_fetched(bt, seq_lens, cfg.block_size)
            if self._index_keys_read is not None:
                self.counts.index_keys_table_total += bt.size * cfg.block_size
                self.counts.index_keys_read_total += \
                    self._index_keys_read(bt, seq_lens, cfg.block_size)

        def finish(out):
            sampled, lps, cids, clps = (a[0] for a in out)
            self.decode_steps += 1
            for req in active:
                if req.state is not RequestState.RUNNING:
                    # stopped on a token, or aborted, while this dispatch
                    # was already issued behind the one that told: its
                    # sample is past the stop and is thrown away
                    self.counts.ahead_discards_total += 1
                    continue
                slot = req.slot
                want_lp = req.sampling.logprobs or req.sampling.top_logprobs > 0
                self._append_token(
                    req, int(sampled[slot]),
                    logprob=float(lps[slot]) if want_lp else None,
                    cand=(cids[slot], clps[slot]) if want_lp else None,
                )

        self._settle(_Inflight(
            "decode_multi", out, finish, {r.slot: r for r in active},
            deferrable=plain, carried=carried))

    def _penalty_buffers(self, active):
        """Build the generated-token penalty buffers for this dispatch, or
        None when no active request uses penalties (the common case pays
        nothing: the operands' layout, which keys the program, has no
        penalty arrays).

        [B, T] token buffer (-1 pad) + first-occurrence mask; T is
        power-of-two bucketed over the longest generation so the
        executable count stays O(log max_model_len)."""
        if not any(
            r.sampling.frequency_penalty or r.sampling.presence_penalty
            for r in active
        ):
            return None
        b = self.config.max_batch_size
        longest = max(r.seq.total_tokens - r.prompt_len for r in active)
        t_cap = max(16, 1 << longest.bit_length())
        t_cap = min(t_cap, max(16, 1 << (self.config.max_model_len - 1).bit_length()))
        ptoks = np.full((b, t_cap), -1, np.int32)
        pfirst = np.zeros((b, t_cap), bool)
        freq = np.zeros(b, np.float32)
        pres = np.zeros(b, np.float32)
        for r in active:
            i = r.slot
            gen = r.seq.tokens[r.prompt_len:]
            n = min(len(gen), t_cap)
            seen: set[int] = set()
            for j, t in enumerate(gen[:n]):
                ptoks[i, j] = t
                if t not in seen:
                    pfirst[i, j] = True
                    seen.add(t)
            freq[i] = r.sampling.frequency_penalty
            pres[i] = r.sampling.presence_penalty
        return ptoks, pfirst, freq, pres

    # ------------------------------------------------------------- lifecycle
    def _append_token(self, req: EngineRequest, token: int, first: bool = False,
                      logprob: Optional[float] = None, cand=None) -> None:
        """Record a sampled token, emit the delta, apply stop conditions.

        The token's KV is *not* yet in the cache — it is computed by the next
        decode step (standard one-step lag).  A block completed by the
        previous token is committed here once its KV landed.
        """
        if req.abort_requested:
            self._finish_slot(req, FinishReason.CANCELLED)
            return
        # the previous tail token's KV just landed (one-step lag); if that
        # filled a block, the block is now fully resident — commit it
        kv_resident = req.seq.total_tokens  # tokens with KV in cache, pre-append
        if not first and kv_resident > 0 and kv_resident % self.config.block_size == 0:
            blk = req.seq.blocks[kv_resident // self.config.block_size - 1]
            if blk.position < len(req.block_ids):
                self.block_manager.commit(
                    req.block_ids[blk.position],
                    blk.sequence_hash,
                    blk.parent_sequence_hash,
                    list(blk.tokens),
                )
        req.seq.append(token)
        req.generated += 1
        self.counts.tokens_generated += 1
        gkey = self._grammar_key(req)
        if gkey is not None and self._grammar is not None:
            # the automaton advances here, on the host, by the sampled
            # token (request-relative state ids)
            req.gstate = self._tables_for(gkey).advance(*req.gstate, token)

        finish: Optional[FinishReason] = None
        st = req.stops
        if token in self.eos_token_ids and not st.ignore_eos and req.generated >= st.min_tokens:
            finish = FinishReason.EOS
        elif token in st.stop_token_ids and req.generated >= st.min_tokens:
            finish = FinishReason.STOP
        elif st.max_tokens is not None and req.generated >= st.max_tokens:
            finish = FinishReason.LENGTH
        elif req.seq.total_tokens >= self.config.max_model_len:
            finish = FinishReason.LENGTH

        out = LLMEngineOutput(
            token_ids=[token], finish_reason=finish, cached_tokens=req.cached_tokens
        )
        out.emitted_at = self._emit_at
        if logprob is not None and (req.sampling.logprobs or req.sampling.top_logprobs):
            out.logprobs = [logprob]
            n = req.sampling.top_logprobs
            if n > 0 and cand is not None:
                ids, lps = cand
                out.top_logprobs = [
                    [(int(i), float(l)) for i, l in zip(ids[:n], lps[:n])]
                ]
        req.emit(out)
        if req.generated == 1 and req.submitted_at:
            self._first_token(req, time.perf_counter())
        if finish is not None:
            self._finish_slot(req, finish, emitted=True)

    def _first_token(self, req: EngineRequest, now: float) -> None:
        """``req`` emitted its first token at ``now``: close its stages.
        submit -> slot (``queue_wait_s``), slot -> first dispatch that
        carried it (turn wait: behind other requests' chunks, the
        alternation's decode turn, a joined prefix still being written),
        first dispatch -> first token (its own chunks, the decode turns
        between them, the program queued behind the one in flight,
        readback, host_post): the three add up to its engine TTFT.  A
        request no dispatch of this engine carried before its first token
        (remote prefill: K/V and token come from a prefill worker) waited
        all of it."""
        req.first_token_at = now
        req.first_token_step = step_timeline.busy_steps_total
        if not req.first_issue_at:
            req.first_issue_at = now
            req.first_issue_step = req.first_token_step
        ttft = now - req.submitted_at
        turn_wait = req.first_issue_at - req.admitted_at
        span = now - req.first_issue_at
        self.counts.first_tokens_total += 1
        self.counts.first_token_seconds_total += ttft
        self.counts.turn_wait_seconds_total += turn_wait
        self.counts.prefill_span_seconds_total += span
        if tracing.enabled() and req.trace:
            tracing.record_span("engine.queue", req.trace,
                                req.submitted_at, req.admitted_at)
            tracing.record_span("engine.turn_wait", req.trace,
                                req.admitted_at, req.first_issue_at)
            tracing.record_span(
                "engine.prefill", req.trace, req.first_issue_at, now,
                {"chunks": req.prefill_chunks,
                 "prompt_tokens": req.prompt_len,
                 "cached_tokens": req.cached_tokens,
                 "first_step": req.first_issue_step,
                 "last_step": req.first_token_step})

    def _cut_short(self, req: EngineRequest) -> None:
        """End a running request because its block space ran out: the
        client sees ``finish_reason: "length"`` like a ``max_tokens`` stop,
        so this counter is the only place the two can be told apart."""
        self.counts.requests_cut_short_total += 1
        self._finish_slot(req, FinishReason.LENGTH)

    def _release_slot(self, req: EngineRequest) -> None:
        """Give back a finished request's slot, reservations and blocks."""
        if req.slot >= 0 and self.slots[req.slot] is req:
            self.slots[req.slot] = None
            if self.draft is not None:
                self.draft.release(req.slot)
        self._pen_cache = None  # live request set changed
        # drop unresolved reservations (commit resolved the rest) so any
        # joiners waiting on us take over instead of hanging
        for h, bid in req.reserved_pairs:
            self.block_manager.unreserve(h, bid)
        req.reserved_pairs = []
        self.block_manager.release(req.block_ids)
        req.block_ids = []

    def _finish_slot(self, req: EngineRequest, reason: FinishReason, emitted: bool = False) -> None:
        fl = self._inflight
        if fl is not None and fl.rows.get(req.slot) is req:
            # a dispatch issued ahead still writes this request's blocks:
            # the client hears of the end now, slot and blocks go back
            # once that dispatch has been read back (_finish_dispatch)
            fl.ended.append(req)
        else:
            self._release_slot(req)
        self._by_id.pop(req.request_id, None)
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        self.counts.requests_finished_total += 1
        if req.first_token_at and tracing.enabled() and req.trace:
            # ends at the stamp its last output carries (the consumer may
            # have closed engine.generate by now), or now if none goes out
            tracing.record_span(
                "engine.decode", req.trace, req.first_token_at,
                self._emit_at if emitted else time.perf_counter(),
                {"tokens": req.generated,
                 "first_step": req.first_token_step,
                 "last_step": step_timeline.busy_steps_total})
        if not emitted:
            req.emit(LLMEngineOutput(token_ids=[], finish_reason=reason,
                                     cached_tokens=req.cached_tokens))

    def _finish(self, req: EngineRequest, reason: FinishReason) -> None:
        """Finish a request that never got a slot."""
        req.state = RequestState.FINISHED
        req.finish_reason = reason
        self.counts.requests_finished_total += 1
        req.emit(LLMEngineOutput(token_ids=[], finish_reason=reason))

    # ------------------------------------------------- disaggregation support
    # All of these run on the engine thread (call via run_on_step from
    # elsewhere).  They are the TPU-native replacement for the reference's
    # NIXL block read/write (vllm patch nixl.py) — device-side gather/scatter
    # with host staging for the DCN hop.

    def held_blocks(self, request_id: str) -> list[int]:
        """Block ids of a finished remote-decode prefill, still resident."""
        return list(self._held.get(request_id, ()))

    def release_held(self, request_id: str) -> None:
        """Transfer-out done: drop the prefill-side block references."""
        ids = self._held.pop(request_id, None)
        if ids:
            self.block_manager.release(ids)

    # --------------------------------------- streamed-handoff commit hooks
    def register_commit_hook(
        self, request_id: str, fn: Callable[[list[int], bool], None]
    ) -> None:
        """Streamed handoff (llm/kv/stream.py): call ``fn(block_ids,
        done)`` on the engine thread after each prefill chunk commits —
        ``block_ids`` is the CUMULATIVE list of this request's committed
        local block ids, ``done=True`` on the final call (which includes
        the partial tail block).  Per-layer callbacks are impossible
        under the jitted scan body, so chunk-boundary granularity is the
        documented fallback (docs/kv_streaming.md).  The hook is
        auto-unregistered after the ``done`` call."""
        self._commit_hooks[request_id] = fn

    def unregister_commit_hook(self, request_id: str) -> None:
        self._commit_hooks.pop(request_id, None)

    def _fire_commit_hook(self, req: EngineRequest, done: bool) -> None:
        fn = self._commit_hooks.get(req.request_id)
        if fn is None:
            return
        bs = self.config.block_size
        n = len(req.block_ids) if done else req.committed_upto // bs
        try:
            fn([int(b) for b in req.block_ids[:n]], done)
        except Exception:
            log.exception("commit hook failed for %s", req.request_id)
        if done:
            self._commit_hooks.pop(req.request_id, None)

    # ------------------------------------------------------ host offload tier
    @staticmethod
    def _persist_generation(model, cache_dtype) -> str:
        """Generation tag for the persistent KV tier: a stable hash of
        everything that determines block-file layout and validity —
        model architecture/dtype, cache dtype, block size.  Any change
        opens a fresh store generation and invalidates the old one."""
        import hashlib
        import json as _json

        mc = getattr(model, "config", None)
        if mc is not None and hasattr(mc, "__dict__"):
            ident = {k: repr(v) for k, v in sorted(vars(mc).items())}
        else:
            ident = {"model": repr(mc)}
        ident["__cache_dtype"] = str(cache_dtype)
        ident["__model_cls"] = type(model).__name__
        blob = _json.dumps(ident, sort_keys=True).encode()
        return hashlib.sha256(blob).hexdigest()[:16]

    def _flush_persist_events(self) -> None:
        """Forward queued persist-tier router events (engine thread only;
        the kv-offload thread enqueues, this drains into the publisher's
        sink which is not thread-safe)."""
        if self.persist_store is None:
            return
        sink = self.block_manager.event_sink
        while self._persist_events:
            ev = self._persist_events.popleft()
            if sink is not None:
                sink(ev)

    def _spill_to_persist(self, hashes: list[int], blocks) -> None:
        """Mirror a host-pool store batch into the persistent tier (runs
        on the kv-offload thread — fsync never blocks the engine loop)."""
        from dynamo_tpu.llm.kv.events import (
            TIER_PERSIST,
            KvRemovedEvent,
            KvStoredEvent,
        )

        try:
            wrote = self.persist_store.spill(hashes, blocks)
        except Exception:  # pragma: no cover - disk full etc; tier degrades
            log.exception("persist spill failed; tier continues without it")
            return
        if wrote:
            self._persist_events.append(
                KvStoredEvent(block_hashes=list(hashes), tier=TIER_PERSIST))
        removed = self.persist_store.drain_removed()
        if removed:
            self._persist_events.append(
                KvRemovedEvent(block_hashes=removed, tier=TIER_PERSIST))

    def _promote_from_persist(self, hashes: list[int]) -> int:
        """Load a persist-tier prefix host-side so the ordinary host-pool
        restore picks it up; returns how many blocks were promoted."""
        try:
            phit = self.persist_store.match_prefix(hashes)
            if not phit:
                return 0
            blocks = self.persist_store.load(phit)
        except KeyError:
            return 0  # raced an eviction / corrupt file — plain miss
        except Exception:  # pragma: no cover - keep admission alive
            log.exception("persist restore failed; treating as miss")
            return 0
        with self._offload_lock:
            self.host_pool.store(phit, blocks)
        return len(phit)

    def _drain_offload(self) -> None:
        """Offload just-evicted device blocks to the host pool.

        The on-device gather MUST dispatch before anything overwrites the
        evicted block ids (single device stream: dispatch order is
        execution order, so the snapshot wins the race by construction).
        The expensive half — device→host readback + host memcpy — runs on
        the kv-offload thread (the CopyStream analogue, kv/layer.rs:619),
        so a request's TTFT never includes another conversation's store.
        """
        if self.host_pool is None:
            return
        self._flush_persist_events()
        if not self._pending_offload:
            return
        pending, self._pending_offload = self._pending_offload, []
        with self._offload_lock:
            # re-evictions of host-resident content only need an LRU
            # refresh — skip the HBM gather for them
            self.host_pool.touch(
                [h for _, h in pending if h in self.host_pool])
            fresh = [(b, h) for b, h in pending if h not in self.host_pool]
        if not fresh:
            return
        bids = [b for b, _ in fresh]
        hashes = [h for _, h in fresh]
        arr = self.gather_blocks_device(bids)    # on-device snapshot
        queued = False
        with self._offload_lock:
            # flag check + enqueue are atomic with close()'s flag set, so
            # a batch can never land behind the shutdown sentinel (where
            # it would be silently dropped and hang a later flush)
            budget = self.config.offload_inflight_blocks
            if not self._offload_closed and (
                self._offload_inflight_blocks + len(bids) <= budget
                # never starve: an oversized single batch may queue alone
                or self._offload_inflight_blocks == 0
            ):
                try:
                    self._offload_q.put_nowait((hashes, arr))
                    self._offload_inflight_blocks += len(bids)
                    queued = True
                except queue.Full:
                    pass  # backpressure: the staging arrays pin HBM
        if not queued:
            # closed, full, or over the block budget — store synchronously
            # so no batch is lost and no further HBM is pinned
            self._store_offload_batch(hashes, arr)

    def _store_offload_batch(self, hashes: list[int], arr) -> None:
        """Readback a gathered [L,n,2,Bs,HkD] snapshot and store it
        host-side (runs on the kv-offload thread, or inline under
        backpressure / flush).

        Three-phase store: reserve (lock), write (NO lock — the bulk
        memcpy must not stall the engine thread's drain/restore behind
        this thread), publish (lock).  ``reserve`` skips hashes another
        in-flight batch already landed (LRU-refresh only), and
        ``publish`` frees rows that lost a store race."""
        np_arr = jax.device_get(arr)  # one batched transfer, numpy leaves
        blocks = jax.tree.map(lambda a: np.moveaxis(a, 1, 0), np_arr)
        with self._offload_lock:
            hids, rows = self.host_pool.reserve(hashes, blocks)
        if not hids:
            return
        try:
            self.host_pool.write_rows(hids, blocks, rows)
        except BaseException:
            with self._offload_lock:
                self.host_pool.abort(hids)  # don't leak reserved capacity
            raise
        with self._offload_lock:
            self.host_pool.publish(hids, [hashes[r] for r in rows])
        if self.persist_store is not None:
            # write-through: published content spills to disk here on the
            # offload thread, so a restart (or a replica pulling the
            # coordinator index) can restore it
            self._spill_to_persist(hashes, blocks)

    def _offload_worker(self) -> None:
        while True:
            item = self._offload_q.get()
            try:
                if item is None:
                    return
                self._store_offload_batch(*item)
            except Exception:  # pragma: no cover - keep the tier alive
                log.exception("async KV offload store failed")
            finally:
                if item is not None:
                    # the snapshot's HBM is released whether or not the
                    # store succeeded — retire its blocks from the
                    # backpressure budget even on failure, else the
                    # budget leaks and degrades every later store to sync
                    with self._offload_lock:
                        self._offload_inflight_blocks -= len(item[0])
                self._offload_q.task_done()

    def flush_host_offload(self) -> None:
        """Block until every queued offload store has landed (tests and
        benches that assert on host-pool contents)."""
        if self.host_pool is None:
            return
        self._drain_offload()
        self._offload_q.join()

    def close(self) -> None:
        """Stop the kv-offload thread (idempotent).  Without this an
        abandoned engine's daemon thread would pin the whole instance —
        params, cache, host pool — for process lifetime.  A dispatch
        still in flight is finished first: nothing stays un-emitted."""
        if getattr(self, "_inflight", None) is not None:
            try:
                self._drain_pipeline()
            except Exception:
                log.exception("finishing the dispatch in flight failed")
                self.fail_all()
        t = getattr(self, "_offload_thread", None)
        if t is not None and t.is_alive():
            # flag first (under the lock _drain_offload enqueues under):
            # after this, drains store inline — nothing can land behind
            # the sentinel.  The sentinel put happens OUTSIDE the lock:
            # it may block on a full queue until the worker drains, and
            # the worker needs the lock for its store phases.
            with self._offload_lock:
                self._offload_closed = True
            self._offload_q.put(None)
            t.join(timeout=30.0)
        self._offload_thread = None
        if getattr(self, "persist_store", None) is not None:
            self.persist_store.close()
        if getattr(self, "_retire_counts", None) is not None:
            self._retire_counts()

    def _restore_from_host(self, req: EngineRequest) -> None:
        """Upload host-resident prefix blocks into the request's fresh
        device blocks, register them, and extend the cached prefix —
        turning a device cache miss into a host hit (TTFT win, ref
        docs/architecture.md:87-93).  Host-pool misses fall through to
        the persistent tier (llm/kv/persist.py): matched blocks are
        promoted host-side first, then ride the same gather/scatter/
        commit path, so a restored prefix is indistinguishable from a
        warm host hit downstream."""
        from dynamo_tpu.engine.counters import persist_counters

        bs = self.config.block_size
        dev = req.cached_tokens // bs
        max_blocks = (req.prompt_len - 1) // bs  # >=1 token must remain
        want = [b.sequence_hash for b in req.seq.blocks[dev:max_blocks]]
        if not want:
            return
        with self._offload_lock:
            host_hit = len(self.host_pool.match_prefix(want))
        promoted = 0
        if self.persist_store is not None and host_hit < len(want):
            promoted = self._promote_from_persist(want[host_hit:])
            if not promoted:
                persist_counters.record_miss()
        with self._offload_lock:
            # the kv-offload thread stores/evicts concurrently; a block
            # still in flight to the pool just misses here (re-prefilled
            # — correct, merely slower).  match+gather under ONE lock
            # hold: a matched block must not be evicted before gather.
            hit = self.host_pool.match_prefix(want)
            if not hit:
                return
            blocks = self.host_pool.gather(hit)  # [n, L, 2, Bs, HkD] (pytree)
        if promoted:
            restored = max(0, len(hit) - host_hit)
            if restored:
                persist_counters.record_restore(restored, restored * bs)
        target = req.block_ids[dev : dev + len(hit)]
        self.scatter_external(
            target, jax.tree.map(lambda a: np.moveaxis(a, 0, 1), blocks)
        )
        for i in range(len(hit)):
            blk = req.seq.blocks[dev + i]
            self.block_manager.commit(
                target[i], blk.sequence_hash, blk.parent_sequence_hash, list(blk.tokens)
            )
        req.cached_tokens += len(hit) * bs

    def _refuse_block_move(self, what: str) -> None:
        """Transfer, streaming and remote prefill speak of the K/V pool; a
        model with a cache layout of its own has none of them."""
        if self._private_cache_layout:
            raise NotImplementedError(
                f"{what}: {type(self.model).__name__} keeps its cache in a "
                "layout the block movers do not know")

    def gather_blocks_device(self, block_ids: list[int]) -> jax.Array:
        """Gather blocks WITHOUT leaving the device: returns a jax.Array
        [L, n, 2, Bs, HkD].  The colocated transfer fast path hands this
        straight to the target engine's scatter — the copy rides ICI (or
        stays on-chip), never touching host RAM (ref: NIXL device WRITE,
        vllm patch nixl.py +394; VERDICT r2 ask #8)."""
        self._refuse_block_move("gather_blocks_device")
        return gather_blocks_padded(self.cache, block_ids)

    def gather_blocks_np(self, block_ids: list[int]):
        """Stage blocks to host RAM: [L, n, 2, Bs, HkD] ndarray (a
        (data, scale) pair of ndarrays for the int8 cache).  Under a
        sharded mesh this all-gathers KV heads — which is exactly the
        TP-resharding the reference needs a Triton kernel for
        (kv_rearrange.py); here the host staging buffer is layout-neutral."""
        self._refuse_block_move("gather_blocks_np")
        out = gather_blocks_padded(self.cache, block_ids)
        return jax.device_get(out)  # one batched transfer, numpy leaves

    def scatter_external(
        self,
        block_ids: list[int],
        blocks: np.ndarray,
        request_id: Optional[str] = None,
    ) -> None:
        """Write transferred blocks into this engine's cache (in place).

        When ``request_id`` is given (remote-prefill ingest), the write is
        validated against that request's live block ownership: if the
        request was aborted meanwhile its blocks may already belong to
        someone else, and a late write must be dropped, not applied.
        """
        self._refuse_block_move("scatter_external")
        if request_id is not None:
            req = self._by_id.get(request_id)
            if (
                req is None
                or req.state is not RequestState.REMOTE_PREFILL
                or not set(block_ids) <= set(req.block_ids)
            ):
                log.warning(
                    "dropping stale KV write for %s (request gone or blocks reassigned)",
                    request_id,
                )
                return
        # `blocks` mirrors the cache pytree (ndarray, or data+scale pair
        # from a quantized peer); structure mismatch = config error
        from dynamo_tpu.ops.kv_quant import QuantKvCache

        if self.cache_quant and type(blocks) is tuple and len(blocks) == 2:
            blocks = QuantKvCache(*blocks)  # wire tuples -> cache pytree
        if self.mesh is not None:
            # shard the staged blocks like the pool so the donated scatter
            # preserves the cache sharding (no step-fn recompiles) — this IS
            # the TP-reshard on ingest (each shard keeps only its heads);
            # ONE device_put straight from host (uploading to the default
            # device first would transfer twice)
            arr = jax.device_put(blocks, self._cache_sharding())
        else:
            arr = jax.device_put(blocks)  # one batched upload, all leaves
        self.cache = scatter_blocks_inplace(self.cache, block_ids, arr)

    def complete_remote_prefill(
        self, request_id: str, first_token: int, error: Optional[str] = None
    ) -> None:
        """Prefill-done notification: the request's KV is now resident in
        this engine's cache; append the prefill-sampled first token and
        enter decode.  (Ref: scheduler stall-until-notified, vllm patch
        scheduler.py hunks + worker.py:212.)"""
        req = self._by_id.get(request_id)
        if req is None or req.state is not RequestState.REMOTE_PREFILL:
            return  # cancelled/finished while prefill ran elsewhere
        if error is not None:
            self._finish_slot(req, FinishReason.ERROR)
            return
        req.computed_tokens = req.prompt_len
        req.state = RequestState.RUNNING
        for blk in req.seq.blocks:
            bid = req.block_ids[blk.position]
            self.block_manager.commit(
                bid, blk.sequence_hash, blk.parent_sequence_hash, list(blk.tokens)
            )
        self._emit_at = time.perf_counter()
        self._append_token(req, int(first_token), first=True)

    def prefix_hit_tokens(self, seq_hashes: list[int], prompt_len: int) -> int:
        """How many prompt tokens would hit the local prefix cache — the
        disagg router's prefix_hit_length input.

        Read-only dict probes (GIL-atomic), safe to call from any thread; a
        concurrently-mutating engine can make the answer slightly stale,
        which only perturbs the routing heuristic, never correctness."""
        return len(
            self.block_manager.match_prefix(seq_hashes, prompt_len)
        ) * self.config.block_size

    def persist_hit_blocks(self, seq_hashes: list[int]) -> int:
        """How many prompt blocks the persist tier could restore locally —
        the transfer-aware router's stream-vs-restore cost input.  0 when
        no persist tier is configured.  Same staleness caveat as
        :meth:`prefix_hit_tokens`: a heuristic input, not a guarantee."""
        if self.persist_store is None or not seq_hashes:
            return 0
        try:
            return len(self.persist_store.match_prefix(list(seq_hashes)))
        except Exception:  # pragma: no cover - probe must never raise
            return 0

    def _pool(self):
        """The part of the cache that is held by block: all of it, but for
        a model that keeps a state per slot beside its ``kv`` (or the leaf
        its ``pool_leaf`` names: a latent cache's ``latent``)."""
        if not self._recurrent:
            return self.cache
        return self.cache[getattr(self.model, "pool_leaf", "kv")]

    def kv_bytes_per_block(self) -> int:
        """Host-staged wire bytes one KV block occupies (all layers, both
        K and V, all parts of a quantized pair) — the router's
        transfer-cost size input.  Derived from the live cache pytree so
        quantization/dtype changes are automatically reflected."""
        leaves = jax.tree.leaves(self._pool())
        # cache leaves are [L, n_blocks, ...]: bytes per block = leaf
        # bytes / n_blocks, summed over parts (a leaf that is not per block,
        # a model's ``moe_counts``, is not the cache's)
        n = self.config.num_blocks
        return sum(int(l.nbytes) // n for l in leaves if l.shape[1] == n)
