"""Flash prefill over the paged cache — the TTFT hot kernel.

The pure-JAX prefill path materialises the full [Hk, G, S, S+P] f32 score
tensor per layer (537MB at S=2048 on a 1B model) and round-trips it
through HBM for the softmax.  This kernel runs the classic flash pattern
instead: the query rows stream in TQ-sized chunks, keys/values arrive as
(a) the chunk's own fresh K/V resident in VMEM and (b) the cached-prefix
blocks double-buffer-DMA'd straight from the paged cache in HBM (same
machinery as the decode kernel), with online-softmax accumulation — scores
never touch HBM.

Semantics match ops.paged_attention.prefill_attention:
  * queries are S contiguous tokens starting at block-aligned ``start[b]``,
  * fresh-fresh attention is causal by chunk index,
  * fresh-prefix attention is full over slots [0, start),
  * query padding rows (index >= seq_len - start) yield 0.

Grid: (B, S/TQ).  GQA is handled per kv-head: q arrives head-group-major
([Hk, G, TQ, D]) and the G query heads fold into the row axis by a
leading-dim merge ([G, TQ, D] -> [G*TQ, D]), so scores and PV are plain
MXU matmuls at any head_dim — a [TQ, G*D] -> [TQ*G, D] lane regroup only
compiles when D is a whole 128-lane tile, which Llama-3.2-1B (D=64) is
not.  SURVEY.md §7 hard part 3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import softcap
from dynamo_tpu.ops.pallas.registry import (
    PREFILL_BLOCKS_PER_CHUNK,
    PREFILL_ROWS_PER_CHUNK,
    prefill_cost_estimate,
    ragged_cost_estimate,
)

__all__ = ["paged_prefill_attention", "ragged_paged_prefill_attention"]

NEG_INF = -1e30


def _kernel(
    seq_ref, start_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref, cache_ref,
    out_ref, acc_ref, m_ref, l_ref, kvbuf, sems,
    **static,    # c, tq, hk, g, d, sm_scale, logit_cap, window
):
    return _kernel_impl(seq_ref, start_ref, bt_ref, layer_ref, q_ref, k_ref,
                        v_ref, cache_ref, None, out_ref, acc_ref, m_ref,
                        l_ref, kvbuf, sems, None, None, **static)


def _kernel_quant(
    seq_ref, start_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref, cache_ref,
    scale_ref, out_ref, acc_ref, m_ref, l_ref, kvbuf, sems, scbuf, scsems,
    **static,
):
    return _kernel_impl(seq_ref, start_ref, bt_ref, layer_ref, q_ref, k_ref,
                        v_ref, cache_ref, scale_ref, out_ref, acc_ref, m_ref,
                        l_ref, kvbuf, sems, scbuf, scsems, **static)


def _kernel_impl(
    # scalar prefetch (SMEM)
    seq_ref,     # [B] int32 — context length incl. fresh tokens
    start_ref,   # [B] int32 — absolute position of q[:, 0]
    bt_ref,      # [B, M] int32
    layer_ref,   # [1] int32
    # inputs
    q_ref,       # [1, Hk, G, TQ, D] VMEM — this grid step's query rows.
    #              The kv-head and group axes LEAD (outside the tiled
    #              minor-2 dims): per-head reads are then plain
    #              leading-index loads — `[1, TQ, Hk, G*D]` with h in the
    #              sublane slot made Mosaic reject the kernel (sublane
    #              slices of extent 1 aren't tile-aligned).
    k_ref,       # [1, S, Hk*D] VMEM — whole fresh K (chunk-resident)
    v_ref,       # [1, S, Hk*D] VMEM
    cache_ref,   # [L, N, 2, Bs, Hk*D] HBM (manual DMA)
    scale_ref,   # [L, N, 2, Hp, Sp] HBM f32 (tile-padded), or None (bf16)
    # outputs
    out_ref,     # [1, Hk, G, TQ, D] VMEM (head-leading, as q_ref)
    # scratch
    acc_ref,     # [Hk, G*TQ, D] f32
    m_ref,       # [Hk, G*TQ, 128] f32
    l_ref,       # [Hk, G*TQ, 128] f32
    kvbuf,       # [2, C, 2, Bs, Hk*D] cache-dtype (double buffer)
    sems,        # [2, C] DMA semaphores
    scbuf,       # [2, C, 2, Hp, Sp] f32, or None
    scsems,      # [2, C] DMA semaphores, or None
    *,
    c: int,
    tq: int,
    hk: int,
    g: int,
    d: int,
    sm_scale: float,
    logit_cap=None,
    window=None,
):
    quant = scale_ref is not None
    bi = pl.program_id(0)
    ri = pl.program_id(1)
    bs = kvbuf.shape[3]
    t = c * bs
    lyr = layer_ref[0]
    prefix = start_ref[bi]                  # cached-prefix token count
    fresh = seq_ref[bi] - prefix            # valid fresh tokens
    n_pref = pl.cdiv(prefix, t)             # data-dependent chunk bound
    # Sliding window: the query at position p sees key j iff 0 <= p - j <
    # window.  This grid step's first query sits at prefix + ri*TQ, so no
    # query of it reads a position before ``band_lo``: the prefix walk
    # begins at the BLOCK that holds it (chunk ci is blocks blk0 + ci*C ..,
    # none before blk0 is fetched) and the fresh walk at its tile.
    fresh0 = 0
    from_blk0 = lambda x: x     # a block index / a position of the walk
    from_pos0 = lambda x: x
    if window is not None:
        band_lo = prefix + ri * tq - (window - 1)
        blk0 = jnp.maximum(band_lo, 0) // bs
        n_pref = pl.cdiv(jnp.maximum(pl.cdiv(prefix, bs) - blk0, 0), c)
        from_blk0 = lambda x: blk0 + x
        from_pos0 = lambda x: blk0 * bs + x
        fresh0 = jnp.maximum(band_lo - prefix, 0) // tq

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    # rows are (group, query)-major: row r is query r % TQ of group r // TQ
    rows = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (g * tq, 1), 0), tq)

    def flash_update(h, s_scores, v_cols, p_scale=None, seen=None):
        """Online-softmax fold of one [G*TQ, TKV] score tile (masked).
        ``p_scale`` [1, TKV] rescales P before the PV product (int8 V
        dequant folded per column; softmax stats use the true probs).
        ``seen`` (the mask, under a window): a query whose band begins
        after this tile has seen no column yet, its m is still NEG_INF and
        exp(NEG_INF - NEG_INF) is 1 - select, do not trust the exp."""
        m_prev = m_ref[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_scores - m_new)
        if seen is not None:
            p = jnp.where(seen, p, 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        pv = jnp.dot(p if p_scale is None else p * p_scale, v_cols,
                     preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv

    def q_head(h):
        # [G, TQ, D] -> [G*TQ, D], pre-scaled f32
        return q_ref[0, h].reshape(g * tq, d).astype(jnp.float32) * sm_scale

    # ---------------------------------------------------- prefix phase (DMA)
    def block_dmas(ci, slot):
        m_table = bt_ref.shape[1]
        out = []
        for i in range(c):  # static unroll: C block copies per chunk
            bid = bt_ref[bi, jnp.minimum(from_blk0(ci * c + i), m_table - 1)]
            out.append(pltpu.make_async_copy(
                cache_ref.at[lyr, bid], kvbuf.at[slot, i], sems.at[slot, i]
            ))
            if quant:  # the block's scale tile rides a second small DMA
                out.append(pltpu.make_async_copy(
                    scale_ref.at[lyr, bid], scbuf.at[slot, i],
                    scsems.at[slot, i]
                ))
        return out

    @pl.when(n_pref > 0)
    def _prologue():
        for dma in block_dmas(0, 0):
            dma.start()

    def pref_body(ci, _):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_pref)
        def _prefetch():
            for dma in block_dmas(ci + 1, jax.lax.rem(ci + 1, 2)):
                dma.start()

        for dma in block_dmas(ci, slot):
            dma.wait()

        kc = kvbuf[slot, :, 0].reshape(t, hk * d).astype(jnp.float32)
        vc = kvbuf[slot, :, 1].reshape(t, hk * d).astype(jnp.float32)
        if quant:
            # padded [Hp, Sp] tiles -> valid [Hk, Bs] -> [Hk, T] by lane
            # concat (token-minor scale layout exists exactly for this —
            # no transpose; the slice is value-level in VMEM)
            sck = jnp.concatenate(
                [scbuf[slot, i, 0][:hk, :bs] for i in range(c)], axis=-1)
            scv = jnp.concatenate(
                [scbuf[slot, i, 1][:hk, :bs] for i in range(c)], axis=-1)
        col = from_pos0(
            ci * t + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1))
        allow = col < prefix                              # [1, T]
        live = allow                                      # V scales' mask
        if window is not None:                            # [G*TQ, T]
            allow = allow & (prefix + ri * tq + rows - col < window)
        # dead prefix slots (past `prefix` in the tail block) may hold
        # non-finite pool garbage; the score mask zeroes their P columns
        # but 0 * NaN-V survives the PV product — zero V rows (and the V
        # scales) for them outright
        vmask = from_pos0(ci * t + jax.lax.broadcasted_iota(
            jnp.int32, (t, 1), 0)) < prefix
        vc = jnp.where(vmask, vc, 0.0)
        if quant:
            scv = jnp.where(live, scv, 0.0)
        for h in range(hk):  # static unroll over kv heads
            s_ = jax.lax.dot_general(
                q_head(h), kc[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )  # [G*TQ, T]
            if quant:
                # K's per-token scale multiplies score columns; V's folds
                # into P inside flash_update's PV product via p_scale
                s_ = s_ * sck[h:h + 1, :]
            if logit_cap is not None:  # Gemma2 attention softcap
                s_ = softcap(s_, logit_cap)
            s_ = jnp.where(allow, s_, NEG_INF)
            flash_update(h, s_, vc[:, h * d:(h + 1) * d],
                         p_scale=scv[h:h + 1, :] if quant else None,
                         seen=None if window is None else allow)
        return 0

    jax.lax.fori_loop(0, n_pref, pref_body, 0)

    # ------------------------------------------------- fresh phase (causal)
    def fresh_body(cj, _):
        col0 = cj * tq
        kc = k_ref[0, pl.ds(col0, tq)].astype(jnp.float32)   # [TQ, Hk*D]
        vc = v_ref[0, pl.ds(col0, tq)].astype(jnp.float32)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)
        # causal by fresh index + clip padding columns
        allow = (col <= ri * tq + rows) & (col < fresh)      # [G*TQ, TQ]
        if window is not None:
            allow = allow & (ri * tq + rows - col < window)
        # fresh padding tokens may be non-finite — zero their V rows
        vc = jnp.where(col0 + jax.lax.broadcasted_iota(
            jnp.int32, (tq, 1), 0) < fresh, vc, 0.0)
        for h in range(hk):
            s_ = jax.lax.dot_general(
                q_head(h), kc[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            if logit_cap is not None:
                s_ = softcap(s_, logit_cap)
            s_ = jnp.where(allow, s_, NEG_INF)
            flash_update(h, s_, vc[:, h * d:(h + 1) * d],
                         seen=None if window is None else allow)
        return 0

    jax.lax.fori_loop(fresh0, ri + 1, fresh_body, 0)

    for h in range(hk):
        denom = jnp.maximum(l_ref[h, :, :1], 1e-9)  # padding rows → 0
        out_ref[0, h] = (
            (acc_ref[h] / denom).reshape(g, tq, d).astype(out_ref.dtype)
        )


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "logit_cap", "rows_per_chunk",
                     "blocks_per_chunk", "window", "interpret"),
)
def paged_prefill_attention(
    q: jax.Array,             # [B, S, H, D]
    k_new: jax.Array,         # [B, S, Hk, D] — fresh keys (pre-RoPE'd)
    v_new: jax.Array,         # [B, S, Hk, D]
    cache: jax.Array,         # [L, N, 2, Bs, Hk*D]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [B, M] int32 (prefix blocks lead the table)
    seq_lens: jax.Array,      # [B] int32
    start: jax.Array,         # [B] int32 — block-aligned chunk start
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    # 128 rows/chunk keeps scratch (acc + m/l at 128-lane padding) + the
    # VMEM-resident fresh K/V well inside the per-core VMEM budget at
    # S=2048, Hk*D=512 — machine-checked by kerncheck's `prefill-8b`
    # geometry (KN001) against registry.VMEM_BUDGET_BYTES
    rows_per_chunk: int = PREFILL_ROWS_PER_CHUNK,
    blocks_per_chunk: int = PREFILL_BLOCKS_PER_CHUNK,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash prefill for S fresh tokens against fresh K/V + cached prefix.
    Returns [B, S, H, D].  ``window`` (static): a sliding window - prefix
    blocks wholly before a grid step's band are not streamed, key tiles
    wholly outside it are skipped, its older edge is masked, and a profile
    shows ``paged_prefill_attention_window``; None traces the kernel as it
    was."""
    from dynamo_tpu.ops.kv_quant import is_quant

    quant = is_quant(cache)
    data, scale = (cache.data, cache.scale) if quant else (cache, None)
    b, s, h, d = q.shape
    l, n, _, bs, hkd = data.shape
    hk = hkd // d
    g = h // hk
    m = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    tq = min(rows_per_chunk, s)
    while s % tq:
        tq //= 2
    c = min(blocks_per_chunk, m)

    # head-group-leading query layout (see kernel docstring):
    # [B, Hk, G, S, D]
    q_in = q.reshape(b, s, hk, g, d).transpose(0, 2, 3, 1, 4)
    k_in = k_new.reshape(b, s, hkd)
    v_in = v_new.reshape(b, s, hkd)

    in_specs = [
        pl.BlockSpec((1, hk, g, tq, d),
                     lambda bi, ri, *_: (bi, 0, 0, ri, 0)),
        pl.BlockSpec((1, s, hkd), lambda bi, ri, *_: (bi, 0, 0)),
        pl.BlockSpec((1, s, hkd), lambda bi, ri, *_: (bi, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # cache stays in HBM
    ]
    scratch = [
        pltpu.VMEM((hk, tq * g, d), jnp.float32),
        pltpu.VMEM((hk, tq * g, 128), jnp.float32),
        pltpu.VMEM((hk, tq * g, 128), jnp.float32),
        pltpu.VMEM((2, c, 2, bs, hkd), data.dtype),
        pltpu.SemaphoreType.DMA((2, c)),
    ]
    operands = [
        seq_lens.astype(jnp.int32),
        start.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_in,
        k_in,
        v_in,
        data,
    ]
    if quant:
        hp, sp = scale.shape[-2:]  # tile-padded (scale_tile(hk, bs))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch += [
            pltpu.VMEM((2, c, 2, hp, sp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, c)),
        ]
        operands.append(scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, s // tq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hk, g, tq, d), lambda bi, ri, *_: (bi, 0, 0, ri, 0)
        ),
        scratch_shapes=scratch,
    )

    # Honest scheduling hint at the static worst case (full-table
    # prefixes) — seq_lens/start are dynamic.
    cost = prefill_cost_estimate(
        b, s, h, hk, d, bs, m, cache_bytes=data.dtype.itemsize,
        quant=quant, rows_per_chunk=rows_per_chunk,
        blocks_per_chunk=blocks_per_chunk, window=window)

    out = pl.pallas_call(
        functools.partial(
            _kernel_quant if quant else _kernel,
            c=c, tq=tq, hk=hk, g=g, d=d, sm_scale=float(sm_scale),
            logit_cap=logit_cap, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, s, d), q.dtype),
        interpret=interpret,
        cost_estimate=cost,
        # the name a profile shows; cellbench's kernel.prefill_attn_roofline
        # matches the prefix paged_prefill_attention, its
        # kernel.window_prefill_roofline paged_prefill_attention_window
        name="paged_prefill_attention" + ("" if window is None else "_window")
        + ("_int8" if quant else ""),
    )(*operands)
    # [B, Hk, G, S, D] -> [B, S, H, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d)


# --------------------------------------------------------- ragged prefill
# Token-budget batched attention over ONE flat token axis holding several
# sequences' chunks.  A row may be a prefill chunk (a contiguous
# block-aligned span) or — in the engine's unified mixed dispatch — a
# DECODE row: one fresh token whose `start` (= context − 1) is NOT
# block-aligned; the per-row prefix DMA streams ceil(start / (C·Bs))
# chunks and the `col < prefix` mask is positionally exact, so the
# partially-filled tail block contributes exactly its resident slots.
# The grid
# walks flat query tiles; a tile may straddle sequences, so row membership
# is derived in-kernel from the span table (row_offsets/row_ends in SMEM)
# instead of a seq_ids vector — 1-D vector gathers are hostile on TPU,
# span comparisons against a 2-D iota are free.  Fresh-fresh attention is
# causal by flat index within a span (flat order == position order); the
# cached prefix streams per ROW: the row loop DMAs each overlapping row's
# own prefix blocks, masked to that row's queries.


def _ragged_kernel(
    start_ref, roff_ref, rend_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref,
    cache_ref, out_ref, acc_ref, m_ref, l_ref, kvbuf, sems,
    **static,    # c, tq, hk, g, d, r_rows, sm_scale, logit_cap, window
):
    return _ragged_kernel_impl(
        start_ref, roff_ref, rend_ref, bt_ref, layer_ref, q_ref, k_ref,
        v_ref, cache_ref, None, out_ref, acc_ref, m_ref, l_ref, kvbuf,
        sems, None, None, **static)


def _ragged_kernel_quant(
    start_ref, roff_ref, rend_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref,
    cache_ref, scale_ref, out_ref, acc_ref, m_ref, l_ref, kvbuf, sems,
    scbuf, scsems,
    **static,
):
    return _ragged_kernel_impl(
        start_ref, roff_ref, rend_ref, bt_ref, layer_ref, q_ref, k_ref,
        v_ref, cache_ref, scale_ref, out_ref, acc_ref, m_ref, l_ref,
        kvbuf, sems, scbuf, scsems, **static)


def _ragged_kernel_impl(
    # scalar prefetch (SMEM)
    start_ref,   # [R] int32 — absolute chunk start per row (prefix length)
    roff_ref,    # [R] int32 — flat index of the row's first token
    rend_ref,    # [R] int32 — flat index one past the row's last REAL token
    bt_ref,      # [R, M] int32
    layer_ref,   # [1] int32
    # inputs
    q_ref,       # [1, Hk, G, TQ, D] VMEM — this grid step's query rows
    k_ref,       # [1, T, Hk*D] VMEM — whole packed fresh K
    v_ref,       # [1, T, Hk*D] VMEM
    cache_ref,   # [L, N, 2, Bs, Hk*D] HBM (manual DMA)
    scale_ref,   # [L, N, 2, Hp, Sp] HBM f32, or None (bf16 cache)
    # outputs
    out_ref,     # [1, Hk, G, TQ, D] VMEM
    # scratch
    acc_ref,     # [Hk, G*TQ, D] f32
    m_ref,       # [Hk, G*TQ, 128] f32
    l_ref,       # [Hk, G*TQ, 128] f32
    kvbuf,       # [2, C, 2, Bs, Hk*D] cache-dtype (double buffer)
    sems,        # [2, C] DMA semaphores
    scbuf,       # [2, C, 2, Hp, Sp] f32, or None
    scsems,      # [2, C] DMA semaphores, or None
    *,
    c: int,
    tq: int,
    hk: int,
    g: int,
    d: int,
    r_rows: int,
    sm_scale: float,
    logit_cap=None,
    window=None,
):
    quant = scale_ref is not None
    ri = pl.program_id(0)
    bs = kvbuf.shape[3]
    t_chunk = c * bs
    lyr = layer_ref[0]
    q0 = ri * tq

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    rows = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (g * tq, 1), 0), tq)
    qflat = q0 + rows                      # [G*TQ, 1] flat query index

    def sid_at(x):
        """Row id per flat index in ``x`` (-1 = padding), from the span
        table — spans are disjoint, so the last matching row wins."""
        def body(r, acc):
            hit = (x >= roff_ref[r]) & (x < rend_ref[r])
            return jnp.where(hit, r, acc)
        return jax.lax.fori_loop(
            0, r_rows, body, jnp.full(x.shape, -1, jnp.int32))

    sid_q = sid_at(qflat)                  # [G*TQ, 1]

    def flash_update(h, s_scores, v_cols, p_scale=None, seen=None):
        m_prev = m_ref[h, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_scores - m_new)
        if seen is not None:    # under a window: see _kernel_impl
            p = jnp.where(seen, p, 0.0)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        pv = jnp.dot(p if p_scale is None else p * p_scale, v_cols,
                     preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv

    def q_head(h):
        return q_ref[0, h].reshape(g * tq, d).astype(jnp.float32) * sm_scale

    # ------------------------------------------------ prefix phase (per row)
    def block_dmas(r, ci, slot, blk0=None):
        m_table = bt_ref.shape[1]
        out = []
        for i in range(c):  # static unroll: C block copies per chunk
            blk = ci * c + i
            if blk0 is not None:    # a windowed walk begins at block blk0
                blk = blk0 + blk
            bid = bt_ref[r, jnp.minimum(blk, m_table - 1)]
            out.append(pltpu.make_async_copy(
                cache_ref.at[lyr, bid], kvbuf.at[slot, i], sems.at[slot, i]
            ))
            if quant:
                out.append(pltpu.make_async_copy(
                    scale_ref.at[lyr, bid], scbuf.at[slot, i],
                    scsems.at[slot, i]
                ))
        return out

    def row_body(r, _):
        prefix = start_ref[r]
        overlap = (q0 < rend_ref[r]) & (q0 + tq > roff_ref[r])

        # Under a window the row's first query in this tile (flat index
        # max(q0, row offset), position prefix + its offset in the span)
        # reads nothing before ``band_lo``: the walk begins at its block.
        blk0 = None
        from_pos0 = lambda x: x
        some = prefix > 0
        if window is not None:
            band_lo = (prefix + jnp.maximum(q0 - roff_ref[r], 0)
                       - (window - 1))
            blk0 = jnp.maximum(band_lo, 0) // bs
            from_pos0 = lambda x: blk0 * bs + x
            some = blk0 * bs < prefix

        @pl.when(overlap & some)
        def _row():
            if window is None:
                n_pref = pl.cdiv(prefix, t_chunk)
            else:
                n_pref = pl.cdiv(pl.cdiv(prefix, bs) - blk0, c)
            for dma in block_dmas(r, 0, 0, blk0):
                dma.start()

            def pref_body(ci, _):
                slot = jax.lax.rem(ci, 2)

                @pl.when(ci + 1 < n_pref)
                def _prefetch():
                    for dma in block_dmas(r, ci + 1, jax.lax.rem(ci + 1, 2),
                                          blk0):
                        dma.start()

                for dma in block_dmas(r, ci, slot, blk0):
                    dma.wait()

                kc = kvbuf[slot, :, 0].reshape(t_chunk, hk * d).astype(
                    jnp.float32)
                vc = kvbuf[slot, :, 1].reshape(t_chunk, hk * d).astype(
                    jnp.float32)
                if quant:
                    sck = jnp.concatenate(
                        [scbuf[slot, i, 0][:hk, :bs] for i in range(c)],
                        axis=-1)
                    scv = jnp.concatenate(
                        [scbuf[slot, i, 1][:hk, :bs] for i in range(c)],
                        axis=-1)
                col = from_pos0(ci * t_chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (1, t_chunk), 1))
                # only this row's queries see this row's prefix slots
                allow = (col < prefix) & (sid_q == r)
                if window is not None:
                    allow = allow & (
                        prefix + qflat - roff_ref[r] - col < window)
                # dead tail-block slots may be non-finite pool garbage —
                # zero their V rows (and V scales); the score mask alone
                # leaves 0 * NaN in the PV product
                vmask = from_pos0(ci * t_chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (t_chunk, 1), 0)) < prefix
                vc = jnp.where(vmask, vc, 0.0)
                if quant:
                    scv = jnp.where(col < prefix, scv, 0.0)
                for h in range(hk):
                    s_ = jax.lax.dot_general(
                        q_head(h), kc[:, h * d:(h + 1) * d],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    if quant:
                        s_ = s_ * sck[h:h + 1, :]
                    if logit_cap is not None:
                        s_ = softcap(s_, logit_cap)
                    s_ = jnp.where(allow, s_, NEG_INF)
                    flash_update(h, s_, vc[:, h * d:(h + 1) * d],
                                 p_scale=scv[h:h + 1, :] if quant else None,
                                 seen=None if window is None else allow)
                return 0

            jax.lax.fori_loop(0, n_pref, pref_body, 0)

        return 0

    jax.lax.fori_loop(0, r_rows, row_body, 0)

    # ------------------------------------------------- fresh phase (causal)
    def fresh_body(cj, _):
        col0 = cj * tq
        kc = k_ref[0, pl.ds(col0, tq)].astype(jnp.float32)   # [TQ, Hk*D]
        vc = v_ref[0, pl.ds(col0, tq)].astype(jnp.float32)
        col = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, tq), 1)
        sid_c = sid_at(col)                                  # [1, TQ]
        # packed-padding tokens (sid -1) may be non-finite — zero their
        # V rows before the PV product
        sid_v = sid_at(col0 + jax.lax.broadcasted_iota(
            jnp.int32, (tq, 1), 0))
        vc = jnp.where(sid_v >= 0, vc, 0.0)
        # same sequence + causal by flat index; padding queries (sid -1)
        # match nothing — fully-masked rows degenerate to a finite
        # uniform-weight PV mean (exp(NEG_INF - NEG_INF) = 1), which the
        # caller discards, matching the base kernel's padding contract
        allow = (sid_c == sid_q) & (col <= qflat) & (sid_q >= 0)
        if window is not None:   # flat gap = position gap inside a span
            allow = allow & (qflat - col < window)
        for h in range(hk):
            s_ = jax.lax.dot_general(
                q_head(h), kc[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            )
            if logit_cap is not None:
                s_ = softcap(s_, logit_cap)
            s_ = jnp.where(allow, s_, NEG_INF)
            flash_update(h, s_, vc[:, h * d:(h + 1) * d],
                         seen=None if window is None else allow)
        return 0

    # a key tile wholly older than q0 - window + 1 is in no query's band
    fresh0 = (0 if window is None
              else jnp.maximum(q0 - (window - 1), 0) // tq)
    jax.lax.fori_loop(fresh0, ri + 1, fresh_body, 0)

    for h in range(hk):
        denom = jnp.maximum(l_ref[h, :, :1], 1e-9)  # keep padding finite
        out_ref[0, h] = (
            (acc_ref[h] / denom).reshape(g, tq, d).astype(out_ref.dtype)
        )


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "logit_cap", "rows_per_chunk",
                     "blocks_per_chunk", "window", "interpret"),
)
def ragged_paged_prefill_attention(
    q: jax.Array,             # [1, T, H, D] — packed fresh queries
    k_new: jax.Array,         # [1, T, Hk, D]
    v_new: jax.Array,         # [1, T, Hk, D]
    cache: jax.Array,         # [L, N, 2, Bs, Hk*D]
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [R, M] int32 — per packed sequence
    seq_lens: jax.Array,      # [R] int32 — context length incl. this chunk
    starts: jax.Array,        # [R] int32 — absolute chunk start per row
    row_offsets: jax.Array,   # [R] int32 — flat index of row's first token
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    rows_per_chunk: int = PREFILL_ROWS_PER_CHUNK,
    blocks_per_chunk: int = PREFILL_BLOCKS_PER_CHUNK,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash ragged (mixed-chunk) attention: T packed fresh tokens of up
    to R sequences against fresh K/V + each row's own cached prefix.
    Rows may be prefill chunks or 1-token decode rows (``starts`` need
    not be block-aligned — see the module comment).  ``window``: as
    ``paged_prefill_attention``'s (``paged_prefill_attention_window_ragged``
    in a profile).  Returns [1, T, H, D]."""
    from dynamo_tpu.ops.kv_quant import is_quant

    quant = is_quant(cache)
    data, scale = (cache.data, cache.scale) if quant else (cache, None)
    _, t, h, d = q.shape
    l, n, _, bs, hkd = data.shape
    hk = hkd // d
    g = h // hk
    m = block_tables.shape[1]
    r_rows = block_tables.shape[0]
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    tq = min(rows_per_chunk, t)
    while t % tq:
        tq //= 2
    c = min(blocks_per_chunk, m)

    q_in = q.reshape(1, t, hk, g, d).transpose(0, 2, 3, 1, 4)
    k_in = k_new.reshape(1, t, hkd)
    v_in = v_new.reshape(1, t, hkd)
    row_ends = row_offsets + (seq_lens - starts)  # one past last real token

    in_specs = [
        pl.BlockSpec((1, hk, g, tq, d), lambda ri, *_: (0, 0, 0, ri, 0)),
        pl.BlockSpec((1, t, hkd), lambda ri, *_: (0, 0, 0)),
        pl.BlockSpec((1, t, hkd), lambda ri, *_: (0, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # cache stays in HBM
    ]
    scratch = [
        pltpu.VMEM((hk, tq * g, d), jnp.float32),
        pltpu.VMEM((hk, tq * g, 128), jnp.float32),
        pltpu.VMEM((hk, tq * g, 128), jnp.float32),
        pltpu.VMEM((2, c, 2, bs, hkd), data.dtype),
        pltpu.SemaphoreType.DMA((2, c)),
    ]
    operands = [
        starts.astype(jnp.int32),
        row_offsets.astype(jnp.int32),
        row_ends.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_in,
        k_in,
        v_in,
        data,
    ]
    if quant:
        hp, sp = scale.shape[-2:]
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        scratch += [
            pltpu.VMEM((2, c, 2, hp, sp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, c)),
        ]
        operands.append(scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(t // tq,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hk, g, tq, d), lambda ri, *_: (0, 0, 0, ri, 0)
        ),
        scratch_shapes=scratch,
    )

    cost = ragged_cost_estimate(
        t, r_rows, h, hk, d, bs, m, cache_bytes=data.dtype.itemsize,
        quant=quant, rows_per_chunk=rows_per_chunk,
        blocks_per_chunk=blocks_per_chunk, window=window)

    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel_quant if quant else _ragged_kernel,
            c=c, tq=tq, hk=hk, g=g, d=d, r_rows=r_rows,
            sm_scale=float(sm_scale), logit_cap=logit_cap, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, hk, g, t, d), q.dtype),
        interpret=interpret,
        cost_estimate=cost,
        name="paged_prefill_attention" + ("" if window is None else "_window")
        + "_ragged" + ("_int8" if quant else ""),
    )(*operands)
    # [1, Hk, G, T, D] -> [1, T, H, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(1, t, h, d)
