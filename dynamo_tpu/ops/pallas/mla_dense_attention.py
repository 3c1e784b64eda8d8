"""Dense latent attention: every query attends to every cached row it may see.

A latent-attention model without an indexer (models/glm_dsa.py, layers of
kind ``none``) keeps its cache in the dense layout of ops/latent_cache.py:
bf16 [L·N, Bs, Wd], one row a token (c_kv ‖ roped k_pe, zero padded to whole
lane groups), one shared key/value head.  The absorbed form for all H heads
of a query: scores of the H latent-space queries on the rows, softmax, and
the probability-weighted sum of the rows' first ``dv`` elements (the caller
expands it per head through kv_b's V half).  The query comes already scaled
(softmax scale, YaRN's and the position's factors folded in), so the kernels
know none of them.

Two kernels, two names, because a profile's operations are read by name
(cellbench's ``kernel.decode_attn_roofline`` / ``kernel.prefill_attn_roofline``):

``mla_dense_decode``   one query a row.  One grid step is one row: it walks
    the row's own blocks of the block table, ``blocks_per_chunk`` whole
    blocks a chunk, one contiguous DMA a block, double buffered (chunk c+1
    in flight while chunk c is computed).  No block past ⌈len/Bs⌉ is
    fetched and an empty slot fetches none (the rule of
    ops/pallas/decode_attention.py).  What a partly owned last chunk leaves
    unwritten in the scratch is stale VMEM: its score columns and its rows
    are *selected* away, never multiplied, and only in that chunk.

``mla_dense_prefill``  the S·H query rows of one sequence's chunk (row
    t·H + h, token t at position ``start + t``) against the sequence's
    context, gathered once into [C, Wd] by the block mover.  Grid (S / tq,
    C / tk), flash attention with one shared head; causality and the
    sequence's length are worked out from positions inside the kernel, so
    no [S, C] mask exists in HBM.  Key tiles wholly after a query tile's
    last position are neither fetched again nor computed; tiles wholly
    before its first need no mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mla_dense_decode", "mla_dense_prefill",
           "DECODE_BLOCKS_PER_CHUNK", "PREFILL_ROWS_PER_TILE",
           "PREFILL_KEYS_PER_TILE"]

# blocks of a row fetched (and scored) together: 16 x 32 = 512 keys, 384 KiB
# a buffer at 384 lanes
DECODE_BLOCKS_PER_CHUNK = 16
# query rows (tokens x heads) and keys of one prefill tile
PREFILL_ROWS_PER_TILE = 1024
PREFILL_KEYS_PER_TILE = 512
NEG_INF = -1e30


def _flash_update(q, keys, ok, m_ref, l_ref, acc_ref, dv: int):
    """One tile of keys into the running softmax of ``q`` [R, D] (scratch
    [R, 128] / [R, 128] / [R, dv]).  ``ok`` bool [R, T] marks what counts;
    None: all of it.  ``keys`` holds nothing non-finite where ``ok``."""
    s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if ok is not None:
        # a row with nothing seen so far keeps m = -1e30, and exp(s - m)
        # would be 1 for its masked keys: weigh by the mask explicitly
        p = jnp.where(ok, p, 0.0)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(keys.dtype), keys[:, :dv],
        preferred_element_type=jnp.float32)


def _flash_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


# ------------------------------------------------------------------ decode


def _decode_kernel(len_ref, bt_ref, q_ref, cache_hbm, out_ref, m_ref, l_ref,
                   acc_ref, buf, sems, *, c: int, dv: int):
    b = pl.program_id(0)
    bs, wd = buf.shape[2], buf.shape[3]
    t = c * bs
    n = len_ref[b]
    # blocks the row owns, clamped to the table: a length beyond it must
    # not index SMEM out of bounds
    owned = jnp.minimum(pl.cdiv(n, bs), bt_ref.shape[1])
    chunks = pl.cdiv(owned, c)

    def block_dmas(ci, slot, wait=False):
        for i in range(c):                    # static: C copies a chunk
            @pl.when(ci * c + i < owned)
            def _copy(i=i):
                dma = pltpu.make_async_copy(
                    cache_hbm.at[bt_ref[b, ci * c + i]], buf.at[slot, i],
                    sems.at[slot, i])
                if wait:
                    dma.wait()
                else:
                    dma.start()

    _flash_init(m_ref, l_ref, acc_ref)

    @pl.when(chunks > 0)
    def _first():
        block_dmas(0, 0)

    def body(ci, _):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < chunks)
        def _prefetch():
            block_dmas(ci + 1, 1 - slot)

        block_dmas(ci, slot, wait=True)
        q = q_ref[0]                                    # [H, Wd]
        whole = (ci + 1) * t <= n

        @pl.when(whole)
        def _all():
            _flash_update(q, buf[slot].reshape(t, wd), None,
                          m_ref, l_ref, acc_ref, dv)

        @pl.when(jnp.logical_not(whole))
        def _tail():
            keys = buf[slot].reshape(t, wd)
            live = ci * t + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0) < n
            # past the row's end lies another sequence's block tail or
            # scratch no copy wrote: 0 x NaN is what no matrix unit masks
            keys = jnp.where(live, keys, jnp.zeros_like(keys))
            col = ci * t + jax.lax.broadcasted_iota(
                jnp.int32, (q.shape[0], t), 1)
            _flash_update(q, keys, col < n, m_ref, l_ref, acc_ref, dv)
        return 0

    jax.lax.fori_loop(0, chunks, body, 0)
    out_ref[0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-9)


@functools.partial(jax.jit, static_argnames=("dv", "blocks_per_chunk",
                                             "interpret"))
def mla_dense_decode(
    q: jax.Array,             # [B, H, Wd] scaled latent-space queries
    cache: jax.Array,         # [R, Bs, Wd] every layer's blocks, flat
    block_tables: jax.Array,  # [B, M] int32 rows of ``cache``
    seq_lens: jax.Array,      # [B] int32 rows each query sees (0: none)
    *, dv: int,
    blocks_per_chunk: int = DECODE_BLOCKS_PER_CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """f32 [B, H, dv]: each row's softmax-weighted sum of the first ``dv``
    elements of its first ``seq_lens`` cached rows; zeros for an empty
    slot."""
    b, h, wd = q.shape
    _, bs, _ = cache.shape
    c = min(blocks_per_chunk, block_tables.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, wd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # cache stays in HBM
        ],
        out_specs=pl.BlockSpec((1, h, dv), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, dv), jnp.float32),
            pltpu.VMEM((2, c, bs, wd), cache.dtype),
            pltpu.SemaphoreType.DMA((2, c)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, c=c, dv=dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
        interpret=interpret,
        name="mla_dense_decode",
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      q.astype(cache.dtype), cache)


# ----------------------------------------------------------------- prefill


def _prefill_kernel(at_ref, q_ref, ctx_ref, out_ref, m_ref, l_ref, acc_ref,
                    *, heads: int, dv: int):
    i, j = pl.program_id(0), pl.program_id(1)
    rows, tk = q_ref.shape[0], ctx_ref.shape[0]
    tq = rows // heads
    start, n = at_ref[0], at_ref[1]
    first, last = start + i * tq, start + i * tq + tq - 1
    k0 = j * tk

    @pl.when(j == 0)
    def _init():
        _flash_init(m_ref, l_ref, acc_ref)

    # every key of the tile is seen by every query of the tile
    clear = (k0 + tk - 1 <= first) & (k0 + tk <= n)

    @pl.when(clear)
    def _all():
        _flash_update(q_ref[...], ctx_ref[...], None, m_ref, l_ref, acc_ref,
                      dv)

    @pl.when(jnp.logical_not(clear) & (k0 <= last) & (k0 < n))
    def _edge():
        at = k0 + jax.lax.broadcasted_iota(jnp.int32, (tq, heads, tk), 2)
        pos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, heads, tk), 0)
        ok = ((at <= pos) & (at < n)).reshape(rows, tk)
        _flash_update(q_ref[...], ctx_ref[...], ok, m_ref, l_ref, acc_ref,
                      dv)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-9)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dv", "rows_per_tile", "keys_per_tile", "interpret"))
def mla_dense_prefill(
    q: jax.Array,             # [S·H, Wd] scaled queries, row t·H + h
    cache: jax.Array,         # [R, Bs, Wd] every layer's blocks, flat
    block_table: jax.Array,   # [Mc] int32 rows of ``cache``: the context
    at: jax.Array,            # [2] int32: position of token 0, rows cached
    *, heads: int, dv: int,
    rows_per_tile: int = PREFILL_ROWS_PER_TILE,
    keys_per_tile: int = PREFILL_KEYS_PER_TILE,
    interpret: bool = False,
) -> jax.Array:
    """f32 [S·H, dv]: token t (at position ``at[0]`` + t) attends to the
    context's rows c <= its position, c < ``at[1]``.  A token that sees no
    row gets zeros."""
    from dynamo_tpu.ops.pallas.latent_cache_dma import gather_blocks

    rows_total, wd = q.shape
    s = rows_total // heads
    _, bs, _ = cache.shape
    tq = max(d for d in range(1, max(1, rows_per_tile // heads) + 1)
             if s % d == 0)
    # whole key tiles: the context is padded with block 0, masked by at[1]
    # (a finite activation like any cached row)
    per_tile = max(1, keys_per_tile // bs)
    mc = block_table.shape[0]
    per_tile = min(per_tile, mc)
    ids = jnp.pad(block_table.astype(jnp.int32), (0, -mc % per_tile))
    ctx = gather_blocks(cache, ids, interpret=interpret).reshape(-1, wd)
    tk = per_tile * bs
    rows = tq * heads

    def key_tile(i, j, at_ref):
        # past the tile's last query, or the sequence's end, the tile
        # before is named again: the pipeline fetches nothing new
        last = jnp.minimum(at_ref[0] + i * tq + tq - 1,
                           jnp.maximum(at_ref[1] - 1, 0))
        return (jnp.minimum(j, last // tk), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // tq, ctx.shape[0] // tk),
        in_specs=[
            pl.BlockSpec((rows, wd), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((tk, wd), key_tile),
        ],
        out_specs=pl.BlockSpec((rows, dv), lambda i, j, *_: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, heads=heads, dv=dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_total, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_dense_prefill",
    )(at.astype(jnp.int32), q.astype(cache.dtype), ctx)
