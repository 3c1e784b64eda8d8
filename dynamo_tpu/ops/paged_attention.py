"""Paged attention over block tables — the engine's core op.

The KV cache is a pool of fixed-size blocks; each sequence owns an ordered
list of block ids (its *block table*).  A single unified op serves prefill,
chunked prefill and decode: the S new tokens of each sequence first scatter
their K/V into the cache, then attend over the sequence's whole context
(cached prefix + themselves) with causal masking by absolute position.

This file holds the pure-JAX implementation: correct on any backend, used
directly on CPU in tests, and as the oracle for the Pallas TPU kernel in
``dynamo_tpu/ops/pallas/``.  On TPU the gather-based fallback is still a
reasonable baseline: XLA fuses the block-table gather with the attention
einsums, and all shapes are static (B, S, M buckets) so everything tiles
onto the MXU.

Reference parity: the reference has no such op in-repo (attention lives in
vLLM); its CUDA surface is block_copy.cu.  This op is the heart of what the
TPU rebuild owns natively (SURVEY.md §7 stage 4).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.kv_quant import (
    QuantKvCache, dequant_layer_slice, is_quant, quantize_kv_rows,
)
from dynamo_tpu.utils.mesh import AXIS_MODEL

__all__ = [
    "ATTENTION_PHASES",
    "attention_impl",
    "softcap",
    "tp_size",
    "write_kv_cache",
    "write_kv_cache_layer",
    "paged_attention",
    "paged_attention_layer",
    "rows_by_length",
    "prefill_attention",
    "ragged_prefill_attention",
    "prefill_program_key",
    "SPARSE_PHASES",
    "sparse_attention_impl",
    "sparse_latent_attention",
]


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma2-style tanh logit softcap (shared by every attention path)."""
    return jnp.tanh(x / cap) * cap


MQ_MAX_S = 8  # multi-query decode kernel: trailing-query count it serves

# phase -> the env knob that pins it to the XLA oracle (tests and A/B runs;
# DYNAMO_DISABLE_PALLAS pins all four)
ATTENTION_PHASES = {
    "decode": "DYNAMO_DISABLE_PALLAS_DECODE",
    "mq": "DYNAMO_DISABLE_PALLAS_MQ",
    "prefill": "DYNAMO_DISABLE_PALLAS_PREFILL",
    "ragged": "DYNAMO_DISABLE_PALLAS_PREFILL",
}


def attention_impl(
    phase: str, *, num_kv_heads: int, block_size: int, quant: bool = False,
    windowed: bool = False, tp: int = 1,
) -> tuple[str, str]:
    """``("pallas" | "xla", why)`` for one attention phase — the ONE
    place the choice is made, before tracing, as a static function of
    the environment, the backend and the geometry.  The dispatch sites
    below and the server's start-up line both read it; nothing on the
    serving path catches a kernel error and falls back, so a kernel
    this says ``pallas`` for either compiles or takes the server down.

    ``windowed``: the static attended span can exceed a sliding window;
    the kernels then run in their windowed form (a row's walk begins at
    the block its band begins in), which the answer names and nothing
    else: int8 K/V and a mesh keep the rules they have.  ``tp``: size of
    the mesh's tensor-parallel axis the call is traced under; the kernels
    then run per kv-head shard under ``shard_map``.
    """
    for var in ("DYNAMO_DISABLE_PALLAS", ATTENTION_PHASES[phase]):
        if os.environ.get(var):
            return "xla", f"{var} is set"
    backend = jax.default_backend()
    if backend != "tpu":
        return "xla", f"backend is {backend}"
    if quant and block_size % 32:
        # int8 payload tiles are (32, 128): Bs % 32 != 0 pads the block's
        # sublane dim and the kernels' per-block DMA cannot slice a
        # partial tile
        return "xla", "int8 KV needs block_size % 32 == 0"
    if tp > 1 and num_kv_heads % tp:
        return "xla", f"{num_kv_heads} kv heads do not split over tp={tp}"
    if tp > 1 and quant:
        # the scale pool's head axis is tile-padded, so an even split of
        # it does not follow the data's head-major lane split
        return "xla", "int8 KV scale pool is not sharded per kv head"
    why = "tpu" if tp == 1 else f"tpu, shard_map over tp={tp}"
    return "pallas", why + ", windowed kernel" if windowed else why


# sparse latent attention (ops/pallas/mla_sparse_attention.py): the phase is
# the kernel's name in a profile, the knob pins it to the XLA gather
SPARSE_PHASES = {
    "decode": "DYNAMO_DISABLE_PALLAS_DECODE",
    "prefill": "DYNAMO_DISABLE_PALLAS_PREFILL",
}


def sparse_attention_impl(phase: str) -> tuple[str, str]:
    """``attention_impl`` for the sparse latent-attention kernel: one
    shared latent head and a cache that is not sharded, so only the
    environment and the backend decide."""
    for var in ("DYNAMO_DISABLE_PALLAS", SPARSE_PHASES[phase]):
        if os.environ.get(var):
            return "xla", f"{var} is set"
    backend = jax.default_backend()
    if backend != "tpu":
        return "xla", f"backend is {backend}"
    return "pallas", "tpu"


def sparse_latent_attention(
    q: jax.Array,        # [N, H, width] latent-space queries
    latent: jax.Array,   # uint32 [L, N_blocks, Bs, 1, W] (ops/latent_cache.py)
    layer: jax.Array,    # scalar int32
    slots: jax.Array,    # [N, K] flat token slots of the layer each query reads
    nvalid: jax.Array,   # [N] how many of them count
    *, sm_scale: float, phase: str,
) -> jax.Array:
    """Each query's softmax-weighted sum of its own list of cache rows,
    f32 [N, H, 2·W] (the row's elements, zero padded)."""
    from dynamo_tpu.ops import latent_cache

    if sparse_attention_impl(phase)[0] != "pallas":
        return latent_cache.sparse_attention_xla(
            q, latent, layer, slots, nvalid, sm_scale)
    from dynamo_tpu.ops.pallas.mla_sparse_attention import (
        mla_sparse_attention,
    )
    from dynamo_tpu.ops.pallas.registry import MLA_SPARSE_LIST_ALIGN

    l, n, bs, _, w = latent.shape
    q_lo, q_hi = latent_cache.split_query(q)
    # a list is sliced out of a flat int32 array, whose tiling is 1,024
    # (a short list, 32 rows under a 32-token prompt, was refused by the
    # chip's compiler); the padding lies past ``nvalid`` and is never read
    pad = -slots.shape[1] % MLA_SPARSE_LIST_ALIGN
    slots = jnp.pad(slots, ((0, 0), (0, pad)))
    o_lo, o_hi = mla_sparse_attention(
        q_lo, q_hi, slots + layer * (n * bs), nvalid,
        latent.reshape(l * n * bs, 1, w), sm_scale=sm_scale, phase=phase)
    return jnp.concatenate([o_lo, o_hi], axis=-1)


def tp_size() -> int:
    """Size of the tensor-parallel axis of the mesh the caller traces
    under (the engine wraps its jitted steps in
    ``jax.sharding.use_abstract_mesh``); 1 with no mesh in scope."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else mesh.shape.get(AXIS_MODEL, 1)


def _per_kv_head(kernel, tp: int, in_specs: tuple, out_spec):
    """Run a Pallas kernel once per tensor-parallel shard.  Mosaic calls
    cannot be partitioned by GSPMD; attention is independent per kv
    head, so each device runs the kernel over its own H/tp query heads
    and Hk/tp kv heads of the cache, tables and lengths replicated."""
    if tp == 1:
        return kernel
    return jax.shard_map(
        kernel, in_specs=in_specs, out_specs=out_spec, check_vma=False)


def rows_by_length(seq_lens: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(order, inverse): the rows of a decode step longest context first,
    empty slots last, ties in slot order — ``x[order]`` groups them,
    ``y[inverse]`` puts results back.  The flash-decode kernel takes G
    consecutive rows a grid step and loops to the longest of them, so rows
    of like length share a group and a group of empty slots does nothing.
    Two sorts of B integers: made once a step, before the layer scan (XLA
    does not move a sort out of a loop's body)."""
    order = jnp.argsort(seq_lens, stable=True, descending=True)
    return order, jnp.argsort(order)


# operand specs under _per_kv_head: arrays split on their head axis
_HEADS3 = P(None, AXIS_MODEL, None)              # [B, H, D]
_HEADS4 = P(None, None, AXIS_MODEL, None)        # [B, S, H|Hk, D]
_CACHE = P(None, None, None, None, AXIS_MODEL)   # [L, N, 2, Bs, Hk*D]
_REPL = P()


def paged_attention_layer(
    q: jax.Array,             # [B, S, H, D]
    cache: jax.Array,         # [L, N, 2, Bs, Hk*D] — full multi-layer cache
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    seq_lens: jax.Array,      # [B] int32
    positions: jax.Array,     # [B, S] int32
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Attention for layer ``layer`` against the full paged cache.

    Dispatch on TPU: S=1 takes the Pallas flash-decode kernel; 1 < S <=
    MQ_MAX_S takes the multi-query variant (the speculative-verify shape).
    BOTH kernel paths require each row's positions to be CONTIGUOUS
    (positions[:, j] == positions[:, 0] + j) — true for every engine
    caller (decode tails, spec verify, prefill chunks); a caller with
    gapped/repeated positions must disable them (DYNAMO_DISABLE_PALLAS /
    DYNAMO_DISABLE_PALLAS_MQ) to get the position-exact oracle, which also
    serves S > MQ_MAX_S and non-TPU backends by materialising the layer
    slice.

    ``window`` (a sliding window) counts ONLY when the STATIC context
    bound (M·Bs) can actually exceed it — a deployment whose max_model_len
    fits inside the window is mathematically full attention and traces the
    kernels' full form.  Where it counts, the kernels take it as a static
    argument (their ``*_window*`` form) and the oracle masks by it.
    """
    b, s, h, d = q.shape
    quant = is_quant(cache)
    data = cache.data if quant else cache
    _, n, _, bs, hkd = data.shape
    hk = hkd // d
    windowed = window is not None and block_tables.shape[1] * bs > window
    if not windowed:
        window = None  # static no-op: full attention is exact here
    tp = tp_size()
    phase = "decode" if s == 1 else "mq" if s <= MQ_MAX_S else None
    if phase and attention_impl(
            phase, num_kv_heads=hk, block_size=bs, quant=quant,
            windowed=windowed, tp=tp)[0] == "pallas":
        from dynamo_tpu.ops.pallas.decode_attention import (
            paged_decode_attention,
            paged_decode_attention_mq,
        )

        if s == 1:
            # the kernel takes the tiling its geometry allows
            # (registry.decode_tiling: 8 rows a group, ~512 KiB a row-chunk)
            kernel = functools.partial(
                paged_decode_attention, sm_scale=sm_scale,
                logit_cap=logit_cap, window=window)
            out = _per_kv_head(
                kernel, tp, (_HEADS3, _CACHE, _REPL, _REPL, _REPL), _HEADS3,
            )(q[:, 0], cache, layer, block_tables, seq_lens)
            return out[:, None]
        # speculative-verify shape: a few trailing queries per row — stream
        # only the owned blocks instead of gathering the padded table
        kernel = functools.partial(
            paged_decode_attention_mq, sm_scale=sm_scale,
            logit_cap=logit_cap, window=window)
        return _per_kv_head(
            kernel, tp, (_HEADS4, _CACHE, _REPL, _REPL, _REPL, _REPL),
            _HEADS4,
        )(q, cache, layer, block_tables, seq_lens, positions[:, 0])

    layer_kv = jax.lax.dynamic_index_in_dim(data, layer, axis=0, keepdims=False)
    if quant:
        layer_sc = jax.lax.dynamic_index_in_dim(
            cache.scale, layer, axis=0, keepdims=False
        )
        layer_kv = dequant_layer_slice(layer_kv, layer_sc, hk)
    k_cache = layer_kv[:, 0].reshape(n, bs, hk, d)
    v_cache = layer_kv[:, 1].reshape(n, bs, hk, d)
    return paged_attention(
        q, k_cache, v_cache, block_tables, seq_lens, positions, sm_scale,
        logit_cap, window=window,
    )


def _prefill_impl(
    phase: str, prefix_blocks: int, span: int, window: int | None, *,
    num_kv_heads: int, block_size: int, quant: bool = False, tp: int = 1,
) -> tuple[str, int | None]:
    """``("pallas" | "xla", the window to mask by)`` for a prefill dispatch
    of ``span`` tokens on its token axis behind ``prefix_blocks`` cached
    blocks: what ``prefill_attention`` ("prefill") and
    ``ragged_prefill_attention`` ("ragged") go by.  On the flash path the
    kernel masks by the window whatever the prefix (it streams the prefix
    by its true length, and ``prefix_blocks`` is the one value 0 there:
    ``prefill_program_key``); on the XLA path a sliding window matters only
    when the STATIC attended span (visible prefix + these tokens) can
    exceed it, and otherwise full attention is exact (no window to mask
    by)."""
    impl = "xla" if span <= 1 else attention_impl(
        phase, num_kv_heads=num_kv_heads, block_size=block_size, quant=quant,
        windowed=window is not None, tp=tp)[0]
    if (impl == "xla" and window is not None
            and prefix_blocks * block_size + span <= window):
        window = None
    return impl, window


def prefill_program_key(
    phase: str, prefix_blocks: int, span: int, window: int | None = None,
    **geometry,
) -> int:
    """The ``prefix_blocks`` a prefill dispatch may hand ``jax.jit`` as a
    static argument, for a model whose forward passes it to the attention
    call below and reads it nowhere else.  On the flash path the kernel
    streams the prefix by its true length and the value decides nothing
    (a window the kernel masks by itself, at every prefix), so every
    prefix gets the one value 0 and two dispatches whose lowered modules
    would be the same meet one program.  On the XLA path it sizes the
    gather and stays as it is.  ``geometry``: ``num_kv_heads``,
    ``block_size``, ``quant`` and
    ``tp``, as ``attention_impl`` takes them."""
    impl, _ = _prefill_impl(phase, prefix_blocks, span, window, **geometry)
    return 0 if impl == "pallas" else prefix_blocks


def prefill_attention(
    q: jax.Array,             # [B, S, H, D] — fresh queries (contiguous from `start`)
    k_new: jax.Array,         # [B, S, Hk, D] — this chunk's keys (pre-cache-write values)
    v_new: jax.Array,         # [B, S, Hk, D]
    cache: jax.Array,         # [L, N, 2, Bs, Hk*D]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    seq_lens: jax.Array,      # [B] int32 — context length incl. new tokens
    start: jax.Array,         # [B] int32 — absolute position of q[:, 0] (block-aligned)
    prefix_blocks: int,       # STATIC: cache blocks holding the cached prefix (bucketed)
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Prefill attention without gathering the sequence's whole block table.

    The chunk's own K/V are right here in registers — only the *cached
    prefix* (prefix-cache hits / earlier chunks) lives in the cache, and it
    spans just ``prefix_blocks`` blocks (a compile-time bucket, usually 0 or
    small).  The padded-table gather this replaces read M×Bs tokens per
    layer regardless of context and dominated TTFT.

    Fresh-fresh attention is causal by chunk index; fresh-prefix is full.
    Padding tail rows (index ≥ seq_len−start) are masked out of everyone's
    context.  Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    hk = k_new.shape[2]
    g = h // hk
    quant = is_quant(cache)
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    data_ = cache.data if quant else cache
    bs_ = data_.shape[3]
    tp = tp_size()
    if window is not None and block_tables.shape[1] * bs_ <= window:
        window = None  # the table cannot hold a context past the window
    impl, window = _prefill_impl(
        "prefill", prefix_blocks, s, window, num_kv_heads=hk,
        block_size=bs_, quant=quant, tp=tp)
    if impl == "pallas":
        # flash path: online softmax, scores never leave VMEM; the cached
        # prefix streams from HBM by its TRUE length (start), so nothing
        # here reads the static prefix_blocks bucket: the engine keys no
        # program by it on this path (``prefill_program_key``)
        from dynamo_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention,
        )
        from dynamo_tpu.ops.pallas.registry import prefill_rows_per_chunk

        kernel = functools.partial(
            paged_prefill_attention, sm_scale=sm_scale, logit_cap=logit_cap,
            rows_per_chunk=prefill_rows_per_chunk(q.shape[2] // tp),
            window=window)
        return _per_kv_head(
            kernel, tp,
            (_HEADS4, _HEADS4, _HEADS4, _CACHE) + (_REPL,) * 4, _HEADS4,
        )(q, k_new, v_new, cache, layer, block_tables, seq_lens, start)

    qg = q.reshape(b, s, hk, g, d).astype(jnp.float32)
    fresh = (seq_lens - start)[:, None, None]  # valid fresh tokens per row

    sf = jnp.einsum("bskgd,btkd->bkgst", qg, k_new.astype(jnp.float32)) * sm_scale
    if logit_cap is not None:  # Gemma2 attention score softcap
        sf = softcap(sf, logit_cap)
    i = jnp.arange(s, dtype=jnp.int32)
    allow_f = (i[None, :, None] >= i[None, None, :]) & (i[None, None, :] < fresh)
    if window is not None:
        # fresh-fresh distance is the chunk-index gap (both offsets from
        # the same block-aligned start).  A padding query more than a
        # window past the last real token would see no column at all, and
        # its NaN row would reach the real rows through the next layer's
        # V (0 * NaN): padding queries keep the unwindowed mask, as finite
        # and as discarded as they are without a window
        padding = i[None, :, None] >= fresh
        allow_f &= ((i[None, :, None] - i[None, None, :]) < window) | padding
    sf = jnp.where(allow_f[:, None, None], sf, -jnp.inf)

    if prefix_blocks == 0:
        probs = jax.nn.softmax(sf, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v_new.astype(jnp.float32))
        return out.reshape(b, s, h, d).astype(q.dtype)

    data = cache.data if quant else cache
    _, n, _, bs, hkd = data.shape
    layer_kv = jax.lax.dynamic_index_in_dim(data, layer, axis=0, keepdims=False)
    ctx = layer_kv[block_tables[:, :prefix_blocks]]  # [B, P, 2, Bs, HkD]
    if quant:
        layer_sc = jax.lax.dynamic_index_in_dim(
            cache.scale, layer, axis=0, keepdims=False
        )
        ctx = dequant_layer_slice(ctx, layer_sc[block_tables[:, :prefix_blocks]], hk)
    t = prefix_blocks * bs
    kp = ctx[:, :, 0].reshape(b, t, hk, d)
    vp = ctx[:, :, 1].reshape(b, t, hk, d)
    sp = jnp.einsum("bskgd,btkd->bkgst", qg, kp.astype(jnp.float32)) * sm_scale
    if logit_cap is not None:
        sp = softcap(sp, logit_cap)
    slot = jnp.arange(t, dtype=jnp.int32)
    allow_p = slot[None, None, :] < start[:, None, None]
    if window is not None:
        # prefix slot t IS absolute position t (the fast path's identity
        # block layout); query i sits at absolute start + i
        q_pos = start[:, None, None] + i[None, :, None]
        allow_p &= ((q_pos - slot[None, None, :]) < window) | padding
    sp = jnp.where(allow_p[:, None, None], sp, -jnp.inf)

    scores = jnp.concatenate([sp, sf], axis=-1)  # [B, Hk, G, S, T+S]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd", probs[..., :t], vp.astype(jnp.float32)
    ) + jnp.einsum(
        "bkgst,btkd->bskgd", probs[..., t:], v_new.astype(jnp.float32)
    )
    return out.reshape(b, s, h, d).astype(q.dtype)


def ragged_prefill_attention(
    q: jax.Array,             # [1, T, H, D] — packed fresh queries (flat token axis)
    k_new: jax.Array,         # [1, T, Hk, D] — packed fresh keys (pre-cache-write)
    v_new: jax.Array,         # [1, T, Hk, D]
    cache: jax.Array,         # [L, N, 2, Bs, Hk*D]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [R, M] int32 — one table per packed sequence
    seq_lens: jax.Array,      # [R] int32 — context length incl. this chunk
    starts: jax.Array,        # [R] int32 — absolute chunk start (block-aligned)
    row_offsets: jax.Array,   # [R] int32 — flat index of each row's first token
    seq_ids: jax.Array,       # [1, T] int32 — owning row per flat token; -1 = pad
    prefix_blocks: int,       # STATIC: max cached-prefix blocks over rows (bucketed)
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Mixed-chunk ragged attention over one flat token axis — the
    unified prefill+decode kernel oracle.

    The token-budget scheduler packs several sequences' chunks onto a
    single [T] axis; ``seq_ids`` names each token's owner.  A row may be
    a *prefill chunk* (L contiguous tokens, ``start`` block-aligned) or a
    *decode row* (1 fresh token at ``start = context − 1``, which need
    NOT be block-aligned: the prefix mask is positionally exact, so the
    partially-filled tail block simply contributes ``start % Bs`` visible
    slots).  Fresh-fresh attention is causal *within* a sequence — flat
    order equals position order inside a span, so the mask is
    seq-equality plus flat-index causality — and tokens never see
    another sequence.  Fresh-prefix attention gathers each ROW's own
    cached-prefix blocks and masks slots at/past that row's ``start``
    (for a decode row that is its full cached context, so
    ``prefix_blocks`` must cover ``ceil(start / Bs)`` blocks).

    This is the pure-JAX oracle (CPU tests, XLA fallback); the per-token
    prefix gather materialises [T, P*Bs] keys, which the Pallas kernel
    (ops/pallas/prefill_attention.py) avoids by streaming each row's
    blocks from HBM.  Padding tokens attend only padding (finite rows,
    discarded by the caller).  Returns [1, T, H, D].
    """
    _, t, h, d = q.shape
    hk = k_new.shape[2]
    g = h // hk
    quant = is_quant(cache)
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    data = cache.data if quant else cache
    _, n, _, bs, hkd = data.shape
    tp = tp_size()
    if window is not None and block_tables.shape[1] * bs <= window:
        window = None  # the table cannot hold a context past the window
    impl, window = _prefill_impl(
        "ragged", prefix_blocks, t, window, num_kv_heads=hk, block_size=bs,
        quant=quant, tp=tp)
    if impl == "pallas":
        from dynamo_tpu.ops.pallas.prefill_attention import (
            ragged_paged_prefill_attention,
        )

        kernel = functools.partial(
            ragged_paged_prefill_attention, sm_scale=sm_scale,
            logit_cap=logit_cap, window=window)
        return _per_kv_head(
            kernel, tp,
            (_HEADS4, _HEADS4, _HEADS4, _CACHE) + (_REPL,) * 5, _HEADS4,
        )(q, k_new, v_new, cache, layer, block_tables, seq_lens, starts,
          row_offsets)

    qg = q[0].reshape(t, hk, g, d).astype(jnp.float32)
    sid = seq_ids[0]                              # [T]
    idx = jnp.arange(t, dtype=jnp.int32)
    same = sid[:, None] == sid[None, :]           # padding pairs with padding
    allow_f = same & (idx[None, :] <= idx[:, None])
    if window is not None:
        # flat gap IS the position gap inside a contiguous span
        allow_f &= (idx[:, None] - idx[None, :]) < window
    sf = jnp.einsum(
        "skgd,tkd->kgst", qg, k_new[0].astype(jnp.float32)
    ) * sm_scale
    if logit_cap is not None:
        sf = softcap(sf, logit_cap)
    sf = jnp.where(allow_f[None, None], sf, -jnp.inf)

    if prefix_blocks == 0:
        probs = jax.nn.softmax(sf, axis=-1)
        out = jnp.einsum(
            "kgst,tkd->skgd", probs, v_new[0].astype(jnp.float32)
        )
        return out.reshape(1, t, h, d).astype(q.dtype)

    r_rows = block_tables.shape[0]
    layer_kv = jax.lax.dynamic_index_in_dim(data, layer, axis=0, keepdims=False)
    ctx = layer_kv[block_tables[:, :prefix_blocks]]  # [R, P, 2, Bs, HkD]
    if quant:
        layer_sc = jax.lax.dynamic_index_in_dim(
            cache.scale, layer, axis=0, keepdims=False
        )
        ctx = dequant_layer_slice(
            ctx, layer_sc[block_tables[:, :prefix_blocks]], hk
        )
    u = prefix_blocks * bs
    kp = ctx[:, :, 0].reshape(r_rows, u, hk, d)
    vp = ctx[:, :, 1].reshape(r_rows, u, hk, d)
    rid = jnp.clip(sid, 0, r_rows - 1)
    kp_t = kp[rid]                                # [T, U, Hk, D] own-row prefix
    vp_t = vp[rid]
    sp = jnp.einsum(
        "skgd,sukd->kgsu", qg, kp_t.astype(jnp.float32)
    ) * sm_scale
    if logit_cap is not None:
        sp = softcap(sp, logit_cap)
    slot = jnp.arange(u, dtype=jnp.int32)
    allow_p = (sid[:, None] >= 0) & (slot[None, :] < starts[rid][:, None])
    if window is not None:
        # prefix slot u IS absolute position u; the query's absolute
        # position is its row start plus its offset within the span
        q_pos = starts[rid] + idx - row_offsets[rid]
        allow_p &= (q_pos[:, None] - slot[None, :]) < window
    sp = jnp.where(allow_p[None, None], sp, -jnp.inf)

    scores = jnp.concatenate([sp, sf], axis=-1)   # [Hk, G, T, U+T]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "kgsu,sukd->skgd", probs[..., :u], vp_t.astype(jnp.float32)
    ) + jnp.einsum(
        "kgst,tkd->skgd", probs[..., u:], v_new[0].astype(jnp.float32)
    )
    return out.reshape(1, t, h, d).astype(q.dtype)


def write_kv_cache_layer(
    cache: jax.Array,    # [L, N, 2, Bs, Hk*D] — the WHOLE paged cache
    layer: jax.Array,    # scalar int32 layer index
    k_new: jax.Array,    # [B, S, Hk, D]
    v_new: jax.Array,    # [B, S, Hk, D]
    slot_idx: jax.Array, # [B, S] int32  flat slot = block_id * Bs + offset; -1 = drop
    block_aligned: bool = False,  # STATIC: rows are Bs-groups, each group
                                  # contiguous from a block-leading slot
    row_tokens: int = 0,  # STATIC: leading tokens written per-row (see below)
) -> jax.Array:
    """Scatter new K/V rows straight into the full multi-layer cache.

    ``row_tokens`` (static) splits the S axis of a ``block_aligned``
    write: the first ``row_tokens`` tokens take the per-row scatter path
    (their slots may sit anywhere in a block) and only the remainder
    takes the block-granular path.  This is the unified mixed-dispatch
    layout: decode rows — one fresh token each at an arbitrary in-block
    offset — lead the flat axis, block-aligned prefill spans follow, and
    the big spans keep the fast write.  ``row_tokens`` must be a block
    multiple so the aligned remainder starts on a span boundary.

    The cache is a scan carry: scattering into it (rather than slicing a
    per-layer view) lets XLA update the buffer in place — the whole-cache
    copy-through-the-loop this replaces dominated decode ITL on TPU.

    With ``block_aligned=True`` (the engine's prefill layout guarantees
    it: chunks start block-aligned and rows are contiguous) the scatter
    collapses to block-granular read-modify-writes: S/Bs big rows instead
    of S small ones (a 2048-token prefill writes 64 block rows per layer,
    not 2048 row scatters — XLA lowers many-small-row scatter to a slow
    sequential loop, which dominated TTFT).  Rows with slot -1 inside a
    partially-valid group keep the EXISTING cache content (the gather+
    select below), honoring the '-1 = drop' contract bit-for-bit.
    Alignment is a caller contract, not data-inspected — callers that
    cannot guarantee it use the default row path.

    For a :class:`QuantKvCache`, the fresh rows are quantized here (one
    scale per row per kv head) and data + scale scatter with the same base
    indices — write-time quantization is what keeps every read path
    (decode kernel, prefill prefix, transfer) a plain rescale.
    """
    if block_aligned and 0 < row_tokens < k_new.shape[1]:
        cache = write_kv_cache_layer(
            cache, layer, k_new[:, :row_tokens], v_new[:, :row_tokens],
            slot_idx[:, :row_tokens], block_aligned=False,
        )
        return write_kv_cache_layer(
            cache, layer, k_new[:, row_tokens:], v_new[:, row_tokens:],
            slot_idx[:, row_tokens:], block_aligned=True,
        )
    if block_aligned and row_tokens >= k_new.shape[1]:
        block_aligned = False  # everything is row-path tokens
    if is_quant(cache):
        b, s, hk, d = k_new.shape
        kq, ks = quantize_kv_rows(k_new)
        vq, vs = quantize_kv_rows(v_new)
        return QuantKvCache(
            _write_layer_rows(cache.data, layer,
                              kq.reshape(b, s, hk * d),
                              vq.reshape(b, s, hk * d),
                              slot_idx, block_aligned),
            _write_layer_scales(cache.scale, layer, ks, vs,
                                slot_idx, block_aligned,
                                bs=cache.data.shape[3]),
        )
    b, s, hk, d = k_new.shape
    return _write_layer_rows(
        cache, layer,
        k_new.astype(cache.dtype).reshape(b, s, hk * d),
        v_new.astype(cache.dtype).reshape(b, s, hk * d),
        slot_idx, block_aligned,
    )


def _write_layer_rows(
    cache: jax.Array,    # [L, N, 2, Bs, R] — R = Hk*D (data) or Hk (scales)
    layer: jax.Array,
    rows_k: jax.Array,   # [B, S, R]
    rows_v: jax.Array,   # [B, S, R]
    slot_idx: jax.Array,
    block_aligned: bool,
) -> jax.Array:
    l, n, two, bs, r = cache.shape
    b, s, _ = rows_k.shape
    rows_k = rows_k.astype(cache.dtype)
    rows_v = rows_v.astype(cache.dtype)
    if block_aligned and s > 1 and s % bs == 0:
        nb = s // bs
        size = l * n * 2  # one-past-the-end: truly dropped by mode="drop"
        first = slot_idx[:, ::bs]                     # [B, nb] block-leading slot
        bid = jnp.where(first >= 0, first // bs, -1)  # [B, nb]
        flat = cache.reshape(size, bs, r)
        base = layer * (n * 2) + bid * 2              # K row of (layer, bid)
        # NOTE: the drop sentinel must be OUT OF BOUNDS (size), never -1 —
        # scatter wraps negative indices like numpy, so -1 would silently
        # corrupt the LAST cache row with padding K/V
        base = jnp.where(bid >= 0, base, size).reshape(-1)
        valid = (slot_idx >= 0).reshape(b * nb, bs, 1)
        gk = rows_k.reshape(b * nb, bs, r)
        gv = rows_v.reshape(b * nb, bs, r)
        if b * nb == 1:
            # One block (a chunk of exactly Bs tokens: every short prompt).
            # XLA turns a scatter of ONE update with an out-of-bounds
            # "drop" into select(in bounds, updated, original) over the
            # whole operand: a second copy of the cache as a temporary of
            # the prefill program (the copy(bitcast) of [L*N*2, Bs, R] that
            # PR 24 met as "the decode program's twin").  K and V of a block
            # are adjacent rows, so the same read-modify-write is one
            # dynamic-update-slice of two rows at a clamped index; a dropped
            # block (first slot -1: no row of it is valid) rewrites what is
            # there.
            at = (jnp.minimum(base[0], size - 2), 0, 0)
            cur = jax.lax.dynamic_slice(flat, at, (2, bs, r))
            new = jnp.where(valid & (base[0] < size),
                            jnp.concatenate([gk, gv]), cur)
            return jax.lax.dynamic_update_slice(flat, new, at).reshape(
                cache.shape)
        # read-modify-write: padding rows inside a partial block preserve
        # the existing cache bytes instead of clobbering them with K/V of
        # padding tokens
        cur_k = flat[jnp.minimum(base, size - 1)]
        cur_v = flat[jnp.minimum(base + 1, size - 1)]
        flat = flat.at[base].set(jnp.where(valid, gk, cur_k), mode="drop")
        flat = flat.at[jnp.where(base < size, base + 1, size)].set(
            jnp.where(valid, gv, cur_v), mode="drop"
        )
        return flat.reshape(cache.shape)
    size = l * n * 2 * bs
    flat = cache.reshape(size, r)
    idx = slot_idx.reshape(-1)
    valid = idx >= 0
    # row for (layer, block=idx//bs, kv, offset=idx%bs) in the flat view
    base = layer * (n * 2 * bs) + (idx // bs) * (2 * bs) + idx % bs
    # OOB sentinel, NOT -1: scatter wraps negative indices (see above)
    k_idx = jnp.where(valid, base, size)
    v_idx = jnp.where(valid, base + bs, size)
    flat = flat.at[k_idx].set(rows_k.reshape(-1, r), mode="drop")
    flat = flat.at[v_idx].set(rows_v.reshape(-1, r), mode="drop")
    return flat.reshape(cache.shape)


def _write_layer_scales(
    scale: jax.Array,     # [L, N, 2, Hp, Sp] f32 (token-minor, tile-padded)
    layer: jax.Array,
    ks: jax.Array,        # [B, S, Hk] per-token K scales
    vs: jax.Array,        # [B, S, Hk]
    slot_idx: jax.Array,  # [B, S]
    block_aligned: bool,
    bs: int,              # block size (tokens) — Sp is padded, so not derivable
) -> jax.Array:
    """Scatter per-token scales into the token-minor scale pool (mirrors
    the data writes in :func:`_write_layer_rows`, index-for-index).  Only
    the valid [:Hk, :Bs] region of each block's padded tile is written."""
    l, n, two, hp, sp = scale.shape
    b, s, hk = ks.shape
    ks = ks.astype(scale.dtype)
    vs = vs.astype(scale.dtype)
    if block_aligned and s > 1 and s % bs == 0:
        nb = s // bs
        size = l * n * 2
        first = slot_idx[:, ::bs]
        bid = jnp.where(first >= 0, first // bs, -1)
        flat = scale.reshape(size, hp, sp)
        base = layer * (n * 2) + bid * 2
        base = jnp.where(bid >= 0, base, size).reshape(-1)
        valid = (slot_idx >= 0).reshape(b * nb, 1, bs)
        # [B, nb, Bs, Hk] -> [B*nb, Hk, Bs] (token-minor tiles)
        gk = jnp.swapaxes(ks.reshape(b * nb, bs, hk), 1, 2)
        gv = jnp.swapaxes(vs.reshape(b * nb, bs, hk), 1, 2)
        cur_k = flat[jnp.minimum(base, size - 1)]
        cur_v = flat[jnp.minimum(base + 1, size - 1)]
        # fold the new tile into the current padded tile: pad lanes/rows
        # keep their existing bytes, padding tokens keep cur
        new_k = cur_k.at[:, :hk, :bs].set(
            jnp.where(valid, gk, cur_k[:, :hk, :bs]))
        new_v = cur_v.at[:, :hk, :bs].set(
            jnp.where(valid, gv, cur_v[:, :hk, :bs]))
        flat = flat.at[base].set(new_k, mode="drop")
        flat = flat.at[jnp.where(base < size, base + 1, size)].set(
            new_v, mode="drop"
        )
        return flat.reshape(scale.shape)
    size = l * n * 2
    flat = scale.reshape(size, hp, sp)
    idx = slot_idx.reshape(-1)
    valid = idx >= 0
    row = layer * (n * 2) + (idx // bs) * 2
    lane = idx % bs
    row_k = jnp.where(valid, row, size)
    row_v = jnp.where(valid, row + 1, size)
    flat = flat.at[row_k, :hk, lane].set(ks.reshape(-1, hk), mode="drop")
    flat = flat.at[row_v, :hk, lane].set(vs.reshape(-1, hk), mode="drop")
    return flat.reshape(scale.shape)


def write_kv_cache(
    k_cache: jax.Array,  # [N, Bs, Hk, D]  block pool
    v_cache: jax.Array,  # [N, Bs, Hk, D]
    k_new: jax.Array,    # [B, S, Hk, D]   fresh keys for the new tokens
    v_new: jax.Array,    # [B, S, Hk, D]
    slot_idx: jax.Array, # [B, S] int32    flat slot = block_id * Bs + offset; -1 = drop (padding)
) -> tuple[jax.Array, jax.Array]:
    """Scatter new K/V rows into the paged cache.  Negative slots (padding
    tokens) are remapped to an out-of-bounds sentinel and dropped —
    scatter WRAPS negative indices like numpy, so -1 itself would write
    the pool's last slot."""
    n, bs, hk, d = k_cache.shape
    flat_idx = slot_idx.reshape(-1)
    flat_idx = jnp.where(flat_idx >= 0, flat_idx, n * bs)
    k_flat = k_cache.reshape(n * bs, hk, d).at[flat_idx].set(
        k_new.astype(k_cache.dtype).reshape(-1, hk, d), mode="drop"
    )
    v_flat = v_cache.reshape(n * bs, hk, d).at[flat_idx].set(
        v_new.astype(v_cache.dtype).reshape(-1, hk, d), mode="drop"
    )
    return k_flat.reshape(n, bs, hk, d), v_flat.reshape(n, bs, hk, d)


def paged_attention(
    q: jax.Array,            # [B, S, H, D]
    k_cache: jax.Array,      # [N, Bs, Hk, D]
    v_cache: jax.Array,      # [N, Bs, Hk, D]
    block_tables: jax.Array, # [B, M] int32 (entries past the sequence end may be any valid id)
    seq_lens: jax.Array,     # [B] int32 — context length including the new tokens
    positions: jax.Array,    # [B, S] int32 — absolute position of each query token
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Attention of S new tokens against their sequence's paged context.

    Causal by absolute position: query at position p sees cache slots
    0..p (the new tokens' K/V must already be in the cache — call
    :func:`write_kv_cache` first).  ``window`` adds sliding-window
    masking (Mistral/Phi3): slot j additionally needs p − j < window.
    Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    _, bs, hk, _ = k_cache.shape
    m = block_tables.shape[1]
    t = m * bs
    g = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)

    # Gather each sequence's context: [B, M, Bs, Hk, D] -> [B, T, Hk, D]
    k_ctx = k_cache[block_tables].reshape(b, t, hk, d)
    v_ctx = v_cache[block_tables].reshape(b, t, hk, d)

    qg = q.reshape(b, s, hk, g, d).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k_ctx.astype(jnp.float32)) * sm_scale
    if logit_cap is not None:
        scores = softcap(scores, logit_cap)

    # mask: slot j visible iff j <= position(query) and j < seq_len
    slot = jnp.arange(t, dtype=jnp.int32)
    lens = jnp.maximum(seq_lens, 1)  # keep padded rows numerically sane
    visible = (slot[None, None, :] <= positions[:, :, None]) & (
        slot[None, None, :] < lens[:, None, None]
    )  # [B, S, T]
    if window is not None:
        # sliding window: the last `window` positions only (HF semantics:
        # attend iff q_pos − k_pos < window)
        visible &= (positions[:, :, None] - slot[None, None, :]) < window
    scores = jnp.where(visible[:, None, None, :, :], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)
