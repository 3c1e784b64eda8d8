"""Sparse latent attention: each query attends to its own list of cache rows.

A latent-attention model with a learned sparse-attention indexer
(models/glm_dsa.py) gives every query token a list of at most ``index_topk``
positions.  This kernel gathers those rows of the paged latent cache and
computes the absorbed form for all heads of one query: scores of the H
latent-space queries on the gathered rows, softmax over the list, and the
probability-weighted sum of the rows (the caller expands it per head through
kv_b's V half).

The cache row (ops/latent_cache.py) is W 32-bit words (384 for GLM-5.2): the
low halves hold W bf16 elements and the high halves W more, so one row is a
whole number of 512-byte lane groups and a single-row DMA never splits a
packed bf16 sublane pair.  The cache comes as [R, 1, W]: the unit axis gives
it a (1, 128) tiling, under which one row is a legal slice (a [R, W] array is
tiled (8, 128) and the chip's compiler refuses a one-row slice of it).  The
kernel never sees the element order: it is given the query split the same
way (``q_lo``/``q_hi``) and returns the weighted sums of both halves.

One grid step is one query.  Its row list comes from HBM into scalar memory,
then the rows are fetched ``rows_per_tile`` at a time, one DMA a row
(``ROW_UNROLL`` of them a turn of the scalar loop), double buffered: tile t+1
is in flight while tile t is computed.  Lists shorter than
the static width are padded by the caller with any valid row; ``nvalid``
masks them and bounds the number of tiles fetched.

The two uses have two names, because a profile's operations are read by
name (cellbench's ``kernel.decode_attn_roofline`` and
``kernel.prefill_attn_roofline``): ``mla_sparse_decode`` is one query a
sequence, ``mla_sparse_prefill`` the queries of a prefill chunk.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import MLA_SPARSE_ROWS_PER_TILE

__all__ = ["mla_sparse_attention", "KERNEL_NAMES"]

KERNEL_NAMES = {"decode": "mla_sparse_decode", "prefill": "mla_sparse_prefill"}
# row DMAs started (and waited for) in one turn of the scalar loop: the
# kernel is bound by issuing them, 43 ns a row one a turn, 28 at 8, 27 at 16
# (one chip, 32 and 160 queries of 2,048 rows: PERF.md, PR 37)
ROW_UNROLL = 8
NEG_INF = -1e30


def _halves(words):
    """[T, W] uint32 -> the two bf16 planes the words pack, [T, W] each."""
    lo = pltpu.bitcast(words << 16, jnp.float32)
    hi = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)
    return lo.astype(jnp.bfloat16), hi.astype(jnp.bfloat16)


def _kernel(nvalid_ref, idx_hbm, qlo_ref, qhi_ref, cache_hbm, olo_ref,
            ohi_ref, idx_smem, buf, idx_sem, sems, *, tk: int, k: int,
            sm_scale: float, unroll: int):
    n = pl.program_id(0)
    nv = nvalid_ref[n]
    tiles = (nv + tk - 1) // tk

    fetch_idx = pltpu.make_async_copy(
        idx_hbm.at[pl.ds(n * k, k)], idx_smem, idx_sem)
    fetch_idx.start()
    fetch_idx.wait()

    def row_copy(t, j, slot):
        return pltpu.make_async_copy(
            cache_hbm.at[pl.ds(idx_smem[t * tk + j], 1)],
            buf.at[slot, pl.ds(j, 1)], sems.at[slot])

    def each_row(do):
        """``do(j)`` for the tile's rows, ``unroll`` of them a loop turn."""
        def group(g, _):
            for u in range(unroll):
                do(g * unroll + u)
            return 0
        jax.lax.fori_loop(0, tk // unroll, group, 0)

    def start_tile(t, slot):
        each_row(lambda j: row_copy(t, j, slot).start())

    def wait_tile(t, slot):
        each_row(lambda j: row_copy(t, j, slot).wait())

    @pl.when(tiles > 0)
    def _first():
        start_tile(0, 0)

    q_lo = qlo_ref[0]                     # [H, W] bf16
    q_hi = qhi_ref[0]
    h, w = q_lo.shape

    def body(t, carry):
        m, l, acc_lo, acc_hi = carry
        slot = t % 2

        @pl.when(t + 1 < tiles)
        def _prefetch():
            start_tile(t + 1, 1 - slot)

        wait_tile(t, slot)
        lo, hi = _halves(buf[slot].reshape(tk, w))   # [tk, W] bf16 each
        # rows past the list's end were fetched from wherever the padding
        # points: they carry no weight, and must carry no NaN either
        live = t * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, 1), 0) < nv
        lo, hi = jnp.where(live, lo, 0), jnp.where(live, hi, 0)
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_lo, lo, dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(q_hi, hi, dims,
                                   preferred_element_type=jnp.float32))
        s = s * sm_scale                  # [H, tk]
        col = t * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < nv, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        pb = p.astype(jnp.bfloat16)
        acc_lo = acc_lo * alpha + jnp.dot(
            pb, lo, preferred_element_type=jnp.float32)
        acc_hi = acc_hi * alpha + jnp.dot(
            pb, hi, preferred_element_type=jnp.float32)
        return m_new, l, acc_lo, acc_hi

    init = (jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, w), jnp.float32), jnp.zeros((h, w), jnp.float32))
    _, l, acc_lo, acc_hi = jax.lax.fori_loop(0, tiles, body, init)
    denom = jnp.maximum(l, 1e-9)
    olo_ref[0] = (acc_lo / denom).astype(olo_ref.dtype)
    ohi_ref[0] = (acc_hi / denom).astype(ohi_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "phase", "rows_per_tile",
                              "interpret"))
def mla_sparse_attention(
    q_lo: jax.Array,        # [N, H, W] bf16: the query on the low halves
    q_hi: jax.Array,        # [N, H, W] bf16: the query on the high halves
    rows: jax.Array,        # [N, K] int32: rows of ``cache`` each query reads
    nvalid: jax.Array,      # [N] int32: how many of a query's K rows count
    cache: jax.Array,       # [R, 1, W] uint32: every layer's rows, flat
    *,
    sm_scale: float,
    phase: str = "decode",
    rows_per_tile: int = MLA_SPARSE_ROWS_PER_TILE,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (o_lo, o_hi), [N, H, W] f32: softmax-weighted sums of the low
    and of the high halves of each query's rows.  A query with ``nvalid`` 0
    gets zeros."""
    n, h, w = q_lo.shape
    k = rows.shape[1]
    tk = min(rows_per_tile, k)
    if k % tk:
        raise ValueError(f"row-list width {k} is not a multiple of {tk}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),            # row lists, HBM
            pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),            # cache, HBM
        ],
        out_specs=[
            pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, h, w), lambda i, *_: (i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.SMEM((k,), jnp.int32),
            pltpu.VMEM((2, tk, 1, w), jnp.uint32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, tk=tk, k=k, sm_scale=sm_scale,
                          unroll=math.gcd(ROW_UNROLL, tk)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, h, w), jnp.float32)] * 2,
        interpret=interpret,
        name=KERNEL_NAMES[phase],
    )(nvalid.astype(jnp.int32), rows.astype(jnp.int32).reshape(n * k),
      q_lo.astype(jnp.bfloat16), q_hi.astype(jnp.bfloat16),
      cache)
