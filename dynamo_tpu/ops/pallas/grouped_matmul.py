"""The experts' grouped matmul where weights are the bound.

``jax.lax.ragged_dot(xs, w, group_sizes)`` as ``grouped_expert_dispatch``
uses it (models/llama.py): ``xs`` [m, K] sorted by group, ``w`` [G, K, N],
row r of the result is ``xs[r] @ w[g]`` for the group g that holds r.  A
decode step or a prefill chunk gives a touched expert 1-64 rows, far under
the ~240 a byte of weights needs to keep the matrix unit busy on a v5e: the
call is a stream of the touched experts' weights, and this kernel is built
to be nothing else.

``grouped_matmul_plan``    from ``group_sizes``, in ``jnp`` ahead of the
                           call: the list of (group, row-tile) pairs that
                           have rows, compacted to a static length.  One
                           plan serves the three projections of a layer.
``grouped_expert_matmul``  the kernel, over one stack of weights or over
                           several of one shape (gate and up share the
                           rows: one call, one read of ``xs``, two weight
                           streams).  Grid (slices of N, pairs): the
                           plan is scalar prefetch, so the weight block's
                           index map names the pair's group and the
                           pipeline fetches the next expert's block while
                           this one is multiplied.  A block is the whole K
                           and a slice of N sized by its bytes
                           (``registry.grouped_matmul_tiling``), read where
                           the expert lies in the stacked array.

A pair's rows are a masked window of its row tile: the tile's TM rows all
meet the expert's block (the matrix unit is idle anyway) and only the
group's are kept.  Consecutive pairs of one row tile share its output
block, zeroed at the tile's first pair, so rows of no group (``held``
elsewhere: they sort last) come out zero and a row of ``xs`` the result
does not depend on may hold anything, NaN too.  Surplus grid steps name
the last pair's blocks again (no DMA) and do nothing.

Accumulation is float32 over the whole K in one ``dot``, rounded once to
the output's dtype, as the matrix unit gives ``ragged_dot``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import (
    GROUPED_MATMUL_MAX_ROWS_PER_GROUP,
    grouped_matmul_cost,
    grouped_matmul_pairs,
    grouped_matmul_tiling,
)

__all__ = ["grouped_matmul_impl", "grouped_matmul_plan",
           "grouped_expert_matmul"]


def grouped_matmul_impl(m: int, groups: int, k: int, n: int, x_dtype,
                        w_dtype) -> bool:
    """Whether a grouped matmul of ``m`` sorted rows over ``groups`` experts
    the router chooses among is the Pallas kernel (else ``lax.ragged_dot``,
    XLA's) — a static function of the environment, the backend, the mesh the
    caller traces under and the shapes, asked before tracing.  The kernel
    where weights are the bound (``m / groups`` rows an expert, up to one row
    tile: ``GROUPED_MATMUL_MAX_ROWS_PER_GROUP``); ``lax.ragged_dot`` above it,
    off the TPU, for other than bf16 rows and weights of whole lanes, and
    under a mesh, where GSPMD partitions it on F."""
    mesh = jax.sharding.get_abstract_mesh()
    bf16 = jnp.dtype(jnp.bfloat16)
    return (not os.environ.get("DYNAMO_DISABLE_PALLAS")
            and jax.default_backend() == "tpu"
            and (mesh.empty or mesh.size == 1)
            and jnp.dtype(x_dtype) == jnp.dtype(w_dtype) == bf16
            and k % 128 == 0 and n % 128 == 0
            and m <= groups * GROUPED_MATMUL_MAX_ROWS_PER_GROUP)


@functools.partial(jax.jit, static_argnames=("m", "tm"))
def grouped_matmul_plan(group_sizes: jax.Array, m: int, tm: int) -> tuple:
    """(groups, tiles, row tiles, lo, hi [P], count [1]) int32: the pairs
    (group, row tile) that share rows, in order, P =
    ``grouped_matmul_pairs`` — a static bound; ``count`` says how many are
    real and the rest repeat the last.  ``lo:hi`` is the group's window of
    the tile's TM rows.  Every row tile has a pair: those past the last
    group's rows get one with an empty window, which zeroes them; it names
    the last group's weights and the last group's tile of ``xs`` (``row
    tiles``: both are in VMEM already, so such a pair fetches nothing).
    (Jitted: a program's layer scans trace it once between them.)"""
    e = group_sizes.shape[0]
    pairs = grouped_matmul_pairs(m, e, tm)
    n_tiles = -(-m // tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]
    # tiles a group meets; the rows of no group are one more "group"
    first = jnp.append(starts // tm, -(-total // tm))
    tiles = jnp.append(
        jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0),
        n_tiles - -(-total // tm))
    upto = jnp.cumsum(tiles)
    count = upto[-1]
    at = jnp.minimum(jnp.arange(pairs, dtype=jnp.int32), count - 1)
    g = jnp.searchsorted(upto, at, side="right",
                         method="compare_all").astype(jnp.int32)
    tile = (first[g] + at - (upto[g] - tiles[g])).astype(jnp.int32)
    real = g < e
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(e, dtype=jnp.int32), 0))
    gi = jnp.minimum(g, e - 1)
    lo = jnp.where(real, jnp.clip(starts[gi] - tile * tm, 0, tm), 0)
    hi = jnp.where(real, jnp.clip(ends[gi] - tile * tm, 0, tm), 0)
    return (jnp.where(real, g, last), tile,
            jnp.where(real, tile, jnp.maximum(total - 1, 0) // tm),
            lo.astype(jnp.int32), hi.astype(jnp.int32),
            jnp.reshape(count, (1,)).astype(jnp.int32))


def _kernel(groups_ref, tiles_ref, rows_ref, lo_ref, hi_ref, count_ref,
            base_ref, x_ref, *refs):
    del groups_ref, rows_ref, base_ref  # read by the index maps
    w_refs, o_refs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    p = pl.program_id(1)
    live = p < count_ref[0]
    tile = tiles_ref[p]
    opens = jnp.logical_or(p == 0, tiles_ref[jnp.maximum(p - 1, 0)] != tile)
    lo, hi = lo_ref[p], hi_ref[p]

    @pl.when(jnp.logical_and(live, opens))
    def _():
        for o_ref in o_refs:
            o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_and(live, hi > lo))
    def _():
        row = jax.lax.broadcasted_iota(jnp.int32, (x_ref.shape[0], 1), 0)
        keep = jnp.logical_and(row >= lo, row < hi)
        for w_ref, o_ref in zip(w_refs, o_refs):
            y = jnp.dot(x_ref[...], w_ref[...],
                        preferred_element_type=jnp.float32)
            o_ref[...] = jnp.where(keep, y.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def grouped_expert_matmul(xs: jax.Array, ws: tuple, plan: tuple,
                          first_group: jax.Array | int = 0, *, tm: int,
                          tn: int | None = None,
                          interpret: bool = False) -> tuple:
    """xs [m, K]; ``ws``: stacks [G, K, N] of one shape (gate and up, or
    down alone: the rows and the plan are read once for all of them);
    ``plan`` of ``grouped_matmul_plan`` for the E groups ``first_group ..
    first_group + E - 1`` of each stack (the stacked form: layer li's
    experts lie at li·E) at ``tm`` rows a tile -> one [m, N] a stack in
    ``xs``'s dtype, rows of no group zero.  ``tn``: the slice of N a weight
    block holds (the registry's rule unless a sweep says otherwise)."""
    m, k = xs.shape
    _, k_w, n = ws[0].shape
    assert k == k_w and all(w.shape == ws[0].shape for w in ws), (
        xs.shape, [w.shape for w in ws])
    tn = tn or grouped_matmul_tiling(
        tm, k, n, ws[0].dtype.itemsize, xs.dtype.itemsize, len(ws))
    groups, tiles, row_tiles, lo, hi, count = plan
    pairs = groups.shape[0]

    def rows(j, p, groups_ref, tiles_ref, rows_ref, *_):
        return (rows_ref[p], 0)

    def weights(j, p, groups_ref, tiles_ref, rows_ref, lo_ref, hi_ref,
                count_ref, base_ref):
        return (base_ref[0] + groups_ref[p], 0, j)

    def out(j, p, groups_ref, tiles_ref, *_):
        return (tiles_ref[p], j)

    cost = grouped_matmul_cost(m, min(pairs, ws[0].shape[0]), k, n,
                               ws[0].dtype.itemsize, xs.dtype.itemsize,
                               len(ws))
    return tuple(pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(n // tn, pairs),
            in_specs=[pl.BlockSpec((tm, k), rows)]
            + [pl.BlockSpec((None, k, tn), weights)] * len(ws),
            out_specs=[pl.BlockSpec((tm, tn), out)] * len(ws)),
        out_shape=[jax.ShapeDtypeStruct((m, n), xs.dtype)] * len(ws),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"], transcendentals=0,
            bytes_accessed=cost["hbm_bytes"]),
        interpret=interpret,
        name="grouped_expert_matmul",
    )(groups, tiles, row_tiles, lo, hi, count,
      jnp.reshape(first_group, (1,)).astype(jnp.int32), xs, *ws))
