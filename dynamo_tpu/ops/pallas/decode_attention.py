"""Flash-decoding over paged KV blocks — the decode-step hot kernel.

Why this exists: a naive paged-attention gathers each sequence's whole
padded context out of the block pool before attending — at batch 64 / 2k
context that is GBs of HBM traffic per step and dominates ITL.  This kernel
instead streams ONLY the blocks each sequence actually owns, directly from
the full multi-layer cache in HBM.

Design (one grid step per GROUP of G sequences, work ∝ each row's context):

  * Grid is (B/G,).  TPU grid steps run sequentially on the core, so the
    per-step fixed cost (DMA issue, loop control, semaphore waits) is paid
    B times if the grid is (B,).  Grouping G sequences per step issues all
    their block DMAs together — up to G×C copies in flight per chunk — and
    amortises the fixed cost G-fold.  At batch 64 this took the 1B-model
    decode step from ~B sequential latency-bound walks to B/G.
  * Inside the kernel a `fori_loop` with a *data-dependent* bound
    (ceil(max(seq_len in group) / chunk)) walks the group's chunks, and
    every block copy is started (and waited for) only if the row owns that
    block: a row fetches ceil(seq_len / Bs) blocks, an empty slot none.
    The part of the K/V scratch no copy wrote holds stale VMEM; dead score
    columns and dead V rows are *selected* away, never multiplied.
  * The loop still runs to the group's longest row, so the caller hands
    the rows over grouped by length (``rows_by_length`` in
    ops/paged_attention.py; ``LlamaModel.forward`` orders a decode step's
    rows once, before the layer scan): a group's bound is then near each of
    its rows' own, and a group of empty slots runs no iteration.  A row's
    output does not depend on which rows share its group.
  * K/V blocks are fetched with manual double-buffered `make_async_copy`
    from the cache in HBM (`pl.ANY`), chunk i+1 in flight while chunk i
    computes.  K and V of a block are adjacent in the cache layout
    [L, N, 2, Bs, HkD], so each block is ONE contiguous DMA.  Block ids
    come from the scalar-prefetched block table in SMEM; the layer is a
    scalar operand, so per-layer K/V is never sliced out.
  * GQA is handled by expanding q to a block-diagonal [H, Hk*D] layout
    outside the kernel, in the query's own dtype and unscaled: scores and
    the PV product are then plain MXU matmuls with no per-head lane
    slicing.
  * Both matmuls take their operands as the cache holds them (bf16 when
    serving; an int8 block is widened to the query's dtype, which holds
    every int8 value) and accumulate in float32: a bf16 x bf16 product is
    exact in float32, ``sm_scale``, the int8 K scale, the soft cap and the
    masks act on the float32 scores.  The float32 probabilities (times the
    int8 V scale) go to the matrix unit as a bf16 head and a bf16
    remainder stacked over the same V tile, so they keep ~16 bits; with a
    float32 cache (tests) the operands are float32 and nothing is split.
  * A row-chunk is ~512 KiB of K/V whatever the row's width
    (``registry.decode_tiling``): what a row-chunk costs beside its DMA is
    paid once a chunk.
  * Online softmax (flash) accumulation in VMEM scratch across chunks.
    The update of a chunk takes R sequences of the group at once
    (``registry.decode_seqs_per_update``): one batched pair of matmuls in
    one basic block, so that R serial chains (matmul, max, exp, sum,
    matmul, accumulator) fill one another's latencies - a branch a row
    left the unit idle between them.  An update is skipped when all its R
    sequences have ended; one that ended before the others runs masked.

  * A static ``window`` (layers of sliding-window attention) gives the
    kernel a second form, ``paged_decode_attention_window*`` in a profile:
    each row's walk begins at the block that holds its first visible key
    (position len - window for a decode row), chunk by chunk from there,
    and the positions before the band are masked inside that block.  With
    ``window=None`` none of that arithmetic is traced.

Semantics match `paged_attention` with S=1: each query row attends over
slots [0, seq_len) of its own block table (the last ``window`` of them
under a window).  Rows with seq_len == 0 yield 0.

Reference parity: the reference's engines delegate decode attention to
vLLM/TRT-LLM paged-attention CUDA kernels; this is the TPU-native
equivalent the rebuild owns (SURVEY.md §7 stage 4, hard part #3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.paged_attention import softcap
from dynamo_tpu.ops.pallas.registry import (
    decode_cost_estimate,
    decode_group_and_chunk,
    decode_seqs_per_update,
    decode_tiling,
)

__all__ = ["paged_decode_attention", "paged_decode_attention_mq"]

NEG_INF = -1e30


def _kernel(
    # scalar prefetch (SMEM)
    seq_ref,     # [B] int32
    q0_ref,      # [B] int32 — absolute position of each row's FIRST query
    bt_ref,      # [B, M] int32
    layer_ref,   # [1] int32
    # inputs
    q_ref,       # [G, S*H, HkD] VMEM — block-diagonal expanded, q's dtype
    cache_ref,   # [L, N, 2, Bs, HkD] HBM (manual DMA)
    # (scale_ref [L, N, 2, Hp, Sp] HBM when quant — spliced via *rest)
    # outputs
    out_ref,     # [G, S*H, HkD] VMEM
    # scratch
    acc_ref,     # [G, S*H, HkD] f32
    m_ref,       # [G, S*H, 128] f32
    l_ref,       # [G, S*H, 128] f32
    kvbuf,       # [2, G, C, 2, Bs, HkD] cache-dtype (double buffer)
    sems,        # [2, G, C] DMA semaphores
    # (scbuf [2, G, C, 2, Hp, Sp] f32 + scsems when quant)
    **static,    # c, g, r, s_q, hk, sm_scale, logit_cap, window
):
    return _kernel_impl(seq_ref, q0_ref, bt_ref, layer_ref, q_ref, cache_ref,
                        None, out_ref, acc_ref, m_ref, l_ref, kvbuf, sems,
                        None, None, **static)


def _kernel_quant(seq_ref, q0_ref, bt_ref, layer_ref, q_ref, cache_ref,
                  scale_ref, out_ref, acc_ref, m_ref, l_ref, kvbuf, sems,
                  scbuf, scsems, **static):
    return _kernel_impl(seq_ref, q0_ref, bt_ref, layer_ref, q_ref, cache_ref,
                        scale_ref, out_ref, acc_ref, m_ref, l_ref, kvbuf,
                        sems, scbuf, scsems, **static)


def _kernel_impl(
    seq_ref, q0_ref, bt_ref, layer_ref, q_ref, cache_ref, scale_ref,
    out_ref, acc_ref, m_ref, l_ref, kvbuf, sems, scbuf, scsems,
    *,
    c: int,
    g: int,
    r: int,
    s_q: int,
    hk: int,
    sm_scale: float,
    logit_cap=None,
    window=None,
):
    gi = pl.program_id(0)
    bs, hkd = kvbuf.shape[4], kvbuf.shape[5]
    h = q_ref.shape[1] // s_q  # rows are (query, head)-major
    t = c * bs
    lyr = layer_ref[0]
    quant = scale_ref is not None
    # what both matmuls take: the cache's dtype as it lies in VMEM (bf16
    # when serving); int8 blocks are widened to the query's dtype
    op_dt = q_ref.dtype if quant else jnp.promote_types(q_ref.dtype,
                                                        kvbuf.dtype)
    split_p = jnp.dtype(op_dt).itemsize < 4

    seq = [seq_ref[gi * g + j] for j in range(g)]
    if window is None:
        # group-wide chunk bound: max seq_len among the G sequences
        max_len = functools.reduce(jnp.maximum, seq)
        num_chunks = pl.cdiv(max_len, t)  # data-dependent loop bound
    # blocks a row owns (0 for an empty slot), clamped to the table width:
    # a caller-side seq_len beyond the table must not index SMEM out of
    # bounds
    owned = [jnp.minimum(pl.cdiv(n, bs), bt_ref.shape[1]) for n in seq]
    if window is None:
        block_of = lambda j, ci, i: ci * c + i
    else:
        # Sliding window: query sq of a row (at q0 + sq) sees key p iff
        # 0 <= q0 + sq - p < window, so nothing before q0 - window + 1 is
        # read by any of them.  Every row walks from ITS OWN first block
        # (chunk ci of row j holds blocks first[j] + ci*C ..), so a row
        # fetches at most window / Bs + 1 blocks whatever its length and
        # whatever rows share its group.
        first = [jnp.maximum(q0_ref[gi * g + j] - (window - 1), 0) // bs
                 for j in range(g)]
        num_chunks = functools.reduce(
            jnp.maximum, [pl.cdiv(owned[j] - first[j], c) for j in range(g)])
        block_of = lambda j, ci, i: first[j] + ci * c + i

    def block_dmas(ci, slot, wait=False):
        """Start, or wait for, the copies of chunk ``ci``: each under the
        one predicate "the row owns this block", the same at start and at
        wait."""
        for j in range(g):          # static unroll over group
            for i in range(c):      # static unroll: C copies per seq per chunk
                @pl.when(block_of(j, ci, i) < owned[j])
                def _copy(j=j, i=i):
                    bid = bt_ref[gi * g + j, block_of(j, ci, i)]
                    # K and V are adjacent in the [.., 2, Bs, HkD] block:
                    # ONE DMA
                    dmas = [pltpu.make_async_copy(
                        cache_ref.at[lyr, bid], kvbuf.at[slot, j, i],
                        sems.at[slot, j, i])]
                    if quant:  # the block's scale tile rides a second small DMA
                        dmas.append(pltpu.make_async_copy(
                            scale_ref.at[lyr, bid], scbuf.at[slot, j, i],
                            scsems.at[slot, j, i]))
                    for dma in dmas:
                        if wait:
                            dma.wait()
                        else:
                            dma.start()

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(num_chunks > 0)
    def _prologue():
        block_dmas(0, 0)

    def body(ci, _):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < num_chunks)
        def _prefetch():
            block_dmas(ci + 1, jax.lax.rem(ci + 1, 2))

        block_dmas(ci, slot, wait=True)

        rows = s_q * h
        for j0 in range(0, g, r):  # static unroll: R sequences an update
            js = range(j0, j0 + r)
            if window is None:
                live = functools.reduce(jnp.maximum, [seq[j] for j in js])
                todo = ci * t < live
            else:   # chunk ci of row j starts at position first[j]*Bs + ci*T
                todo = functools.reduce(jnp.logical_or, [
                    first[j] * bs + ci * t < seq[j] for j in js])

            # skip chunks past the end of all R sequences (and zero-length
            # rows: their acc/l stay 0 -> output 0).  A sequence that ended
            # before the others of its update runs it fully masked.
            @pl.when(todo)
            def _update(j0=j0, js=js):
                jsl = slice(j0, j0 + r)
                q = q_ref[jsl].astype(op_dt)  # [R, S*H, HkD]
                k = kvbuf[slot, jsl, :, 0].reshape(r, t, hkd).astype(op_dt)
                v = kvbuf[slot, jsl, :, 1].reshape(r, t, hkd).astype(op_dt)

                # Slots at/past seq_len hold whatever the pool holds (pad
                # lanes of a live block) or whatever the scratch held (a
                # block of the chunk the row does not own is not copied:
                # stale VMEM, any bit pattern).  Both are SELECTED away,
                # never multiplied: the score mask below picks NEG_INF for
                # their columns of s and 0 for their columns of p, and
                # because 0 * garbage-V is still garbage when V is
                # non-finite, their V rows (and the V scales) are picked to
                # 0 here.  Keep the `jnp.where`s.
                slot_pos = ci * t + jax.lax.broadcasted_iota(
                    jnp.int32, (t, 1), 0)
                if window is None:
                    v_live = jnp.stack([slot_pos < seq[j] for j in js])
                else:
                    v_live = jnp.stack([first[j] * bs + slot_pos < seq[j]
                                        for j in js])
                v = jnp.where(v_live, v, jnp.zeros_like(v))

                s = jax.lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32
                ) * sm_scale  # [R, S*H, T] f32
                if quant:
                    # int8 KV: k rows carry a per-(token, kv-head) scale.
                    # Column t of s uses k row t whose scale depends on the
                    # query's kv head — slice each block's padded [Hp, Sp]
                    # tile down to its valid [Hk, Bs] region (value-level
                    # slice in VMEM; the DMA moved the whole aligned tile),
                    # build [H, T] tiles by lane-concat, then repeat each
                    # kv head's row for its G query heads (q rows are
                    # kv-head-major).  V's scale folds into P before the PV
                    # matmul (not into l: softmax stats use true probs).
                    def scales(j, kv):
                        sc = jnp.concatenate(
                            [scbuf[slot, j, i, kv][:hk, :bs]
                             for i in range(c)], axis=-1)     # [Hk, T]
                        sc = jnp.repeat(sc, h // hk, axis=0)  # [H, T]
                        # row layout is (query, head)-major
                        return jnp.concatenate([sc] * s_q, axis=0)

                    s = s * jnp.stack([scales(j, 0) for j in js])
                    scv = jnp.stack([scales(j, 1) for j in js])
                if logit_cap is not None:  # Gemma2 attention softcap
                    s = softcap(s, logit_cap)
                pos = ci * t + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
                # causal per query: query sq (row sq*H + h) sits at absolute
                # position q0 + sq and sees cache slots <= that position
                sq = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0) // h
                if window is None:
                    owned_pos = jnp.stack([pos < seq[j] for j in js])
                    seen = owned_pos & jnp.stack(
                        [pos <= q0_ref[gi * g + j] + sq for j in js])
                else:
                    # each row's own positions, and the band's older edge
                    own = [first[j] * bs + pos for j in js]
                    q_pos = [q0_ref[gi * g + j] + sq for j in js]
                    owned_pos = jnp.stack(
                        [p < seq[j] for p, j in zip(own, js)])
                    seen = owned_pos & jnp.stack(
                        [(p <= qp) & (qp - p < window)
                         for p, qp in zip(own, q_pos)])
                s = jnp.where(seen, s, NEG_INF)

                m_prev = m_ref[jsl, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                # a row with no column to see yet keeps m = NEG_INF, and
                # exp(NEG_INF - NEG_INF) is 1: select, do not trust the exp
                p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                l_ref[jsl] = l_ref[jsl] * alpha + jnp.sum(
                    p, axis=2, keepdims=True)
                m_ref[jsl] = jnp.broadcast_to(m_new, (r,) + m_ref.shape[1:])
                if quant:
                    # dead-slot V scales may be non-finite (pad lanes of
                    # the scale tile) — see the V zeroing above
                    p = p * jnp.where(owned_pos, scv, 0.0)
                if split_p:
                    # a bf16 head and a bf16 remainder of the f32 weights,
                    # stacked over the same V tile: ~16 bits of p reach
                    # the accumulator (stacked in f32, where a row is a
                    # whole sublane tile, then narrowed once)
                    head = p.astype(op_dt).astype(jnp.float32)
                    p = jnp.concatenate([head, p - head], axis=1)
                pv = jax.lax.dot_general(
                    p.astype(op_dt), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
                if split_p:
                    pv = pv[:, :rows] + pv[:, rows:]
                acc_ref[jsl] = acc_ref[jsl] * alpha + pv
        return 0

    jax.lax.fori_loop(0, num_chunks, body, 0)

    for j in range(g):
        denom = jnp.maximum(l_ref[j, :, :1], 1e-9)
        out_ref[j] = (acc_ref[j] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "logit_cap", "blocks_per_chunk",
                     "seqs_per_group", "window", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,             # [B, H, D]
    cache,                    # [L, N, 2, Bs, Hk*D] cache — or QuantKvCache
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    seq_lens: jax.Array,      # [B] int32
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    blocks_per_chunk: int | None = None,
    seqs_per_group: int | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One decode step of attention for B sequences.  Returns [B, H, D]."""
    return paged_decode_attention_mq(
        q[:, None], cache, layer, block_tables, seq_lens,
        seq_lens - 1,  # the single query is the sequence tail
        sm_scale=sm_scale, logit_cap=logit_cap,
        blocks_per_chunk=blocks_per_chunk, seqs_per_group=seqs_per_group,
        window=window, interpret=interpret,
    )[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "logit_cap", "blocks_per_chunk",
                     "seqs_per_group", "seqs_per_update", "window",
                     "interpret"),
)
def paged_decode_attention_mq(
    q: jax.Array,             # [B, S, H, D] — S contiguous trailing queries
    cache,                    # [L, N, 2, Bs, Hk*D] cache — or QuantKvCache
    layer: jax.Array,         # scalar int32
    block_tables: jax.Array,  # [B, M] int32
    seq_lens: jax.Array,      # [B] int32 — context incl. the new queries
    q0_pos: jax.Array,        # [B] int32 — absolute position of q[:, 0]
    sm_scale: float | None = None,
    logit_cap: float | None = None,
    blocks_per_chunk: int | None = None,
    seqs_per_group: int | None = None,
    seqs_per_update: int | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Multi-query flash decode: S queries per row (query j at position
    q0_pos+j, causal) against the row's owned blocks — the speculative
    verify pass and other short non-block-aligned S>1 steps stream only
    live KV instead of gathering the padded table.  Returns [B, S, H, D].
    Rows whose real query count is < S put padding at the tail; their
    outputs are finite garbage the caller discards.

    ``seqs_per_group`` / ``blocks_per_chunk`` / ``seqs_per_update`` left
    None follow the geometry (``registry.decode_tiling``: 8 rows a group
    and ~512 KiB of K/V a row-chunk, less where the kernel's scratch would
    not fit; ``registry.decode_seqs_per_update``).

    ``window`` (static): a sliding window - query j sees key p iff
    0 <= q0_pos + j - p < window.  A row's walk then begins at the block
    that holds q0_pos - window + 1, no block before it is fetched, and the
    call shows in a profile as ``paged_decode_attention_window_mq``; None
    traces the full-attention kernel as it was."""
    from dynamo_tpu.ops.kv_quant import is_quant

    quant = is_quant(cache)
    data, scale = (cache.data, cache.scale) if quant else (cache, None)
    b, s_q, h, d = q.shape
    l, n, _, bs, hkd = data.shape
    hk = hkd // d
    m = block_tables.shape[1]
    g_heads = h // hk
    rows = s_q * h
    if sm_scale is None:
        sm_scale = 1.0 / (d**0.5)
    spg, bpc = decode_tiling(h, hkd, bs, data.dtype.itemsize,
                             q.dtype.itemsize)
    seqs_per_group = seqs_per_group or spg
    blocks_per_chunk = blocks_per_chunk or bpc
    g, c = decode_group_and_chunk(b, s_q, m, seqs_per_group, blocks_per_chunk)
    r = decode_seqs_per_update(g, c, bs, seqs_per_update)

    # Block-diagonal q expansion: row for (query sq, head (k, gh)) lives in
    # kv-head k's D-wide column slot; zeros elsewhere.  [B, S, H, D] ->
    # [B, S*H, Hk*D] in q's dtype, unscaled (the kernel scales the f32
    # scores), columns ordered (kv_head, d) to match the cache.
    q_exp = jnp.einsum("bskgd,ke->bskged",
                       q.reshape(b, s_q, hk, g_heads, d),
                       jnp.eye(hk, dtype=q.dtype))
    q_exp = q_exp.reshape(b, rows, hkd)

    in_specs = [
        pl.BlockSpec((g, rows, hkd), lambda i, *_: (i, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # cache stays in HBM
    ]
    scratch = [
        pltpu.VMEM((g, rows, hkd), jnp.float32),
        pltpu.VMEM((g, rows, 128), jnp.float32),
        pltpu.VMEM((g, rows, 128), jnp.float32),
        pltpu.VMEM((2, g, c, 2, bs, hkd), data.dtype),
        pltpu.SemaphoreType.DMA((2, g, c)),
    ]
    operands = [
        seq_lens.astype(jnp.int32),
        q0_pos.astype(jnp.int32),
        block_tables.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_exp,
        data,
    ]
    if quant:
        hp, sp = scale.shape[-2:]  # tile-padded (scale_tile(hk, bs))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # scales in HBM
        scratch += [
            pltpu.VMEM((2, g, c, 2, hp, sp), jnp.float32),
            pltpu.SemaphoreType.DMA((2, g, c)),
        ]
        operands.append(scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b // g,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((g, rows, hkd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=scratch,
    )

    # Honest scheduling hint: seq_lens are dynamic, so price the static
    # worst case (every row at full-table context).
    cost = decode_cost_estimate(
        b, s_q, h, hk, d, bs, m, cache_bytes=data.dtype.itemsize,
        quant=quant, blocks_per_chunk=blocks_per_chunk,
        q_bytes=q.dtype.itemsize, window=window)

    out = pl.pallas_call(
        functools.partial(_kernel_quant if quant else _kernel, c=c, g=g,
                          r=r, s_q=s_q, hk=hk, sm_scale=sm_scale,
                          logit_cap=logit_cap, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, hkd), q.dtype),
        interpret=interpret,
        cost_estimate=cost,
        # the name a profile shows; cellbench's kernel.decode_attn_roofline
        # matches the prefix paged_decode_attention, its
        # kernel.window_decode_roofline paged_decode_attention_window
        name=("paged_decode_attention_mq" if window is None
              else "paged_decode_attention_window_mq")
        + ("_int8" if quant else ""),
    )(*operands)

    # Collapse the block-diagonal layout back to [B, S, H, D].
    out = out.reshape(b, s_q, hk, g_heads, hk, d)
    out = jnp.einsum("bskged,ke->bskgd", out, jnp.eye(hk, dtype=out.dtype))
    return out.reshape(b, s_q, h, d)
