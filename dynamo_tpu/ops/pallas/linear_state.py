"""The decode step of the gated delta rule, the state read once and written
once.

``ops/linear_state.py::delta_rule_step`` is the arithmetic; under XLA it is
three passes over a layer's state (268 MB at 64 slots x 64 heads x 128 x 128
float32), because the rank-one update needs ``u``, which needs a reduction
over the whole of a head's matrix.  Here a head's matrix waits in VMEM between
the two:

``state_update``  the whole leaf ``state`` [L, slots, H, dk, dv] with the
                  layer's row ``layer`` updated by one token a slot, and
                  ``o`` [slots, H, dv] float32.  The leaf is donated and
                  aliased: the kernel writes the layer's row where it lies.

A grid step is one slot and ``LINEAR_STATE_HEADS_PER_STEP`` heads: their
matrices come in as one block, double buffered by the pipeline, and leave as
one.  A head's q, k and g are needed along the matrix's *rows* (dk on the
sublanes), so the group's 3 x heads vectors are stacked as rows of one
[128, dk] tile and transposed once a step.  Every product with the state is
a multiply and a sum on the vector unit: **no ``dot``** — Mosaic multiplies
float32 operands in one bf16 pass of the matrix unit (PERF.md §6, PR 47), and
a state rounded to bf16 on every read is a different model.

A ``fresh`` slot starts from zeros whatever it held; a slot that is not
``alive`` keeps its matrices bit for bit — they are not moved at all: its
grid steps name the block that is in VMEM already (``_resident``) — and its
``o`` is zero.  Both are per-slot scalars, prefetched.  The grid runs in
order ("arbitrary" on both axes): the naming leans on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import (
    linear_state_cost,
    linear_state_heads_per_step,
)

__all__ = ["state_update", "state_update_supported"]

F32 = jnp.float32
# the tile q | k | g are transposed in: the vector unit's lanes
LANES = 128


def state_update_supported(heads: int, dk: int, dv: int, dtype) -> bool:
    """Whether the kernel takes this geometry: a float32 state whose matrices
    are whole (8, 128) tiles with dk a whole number of transposed tiles, and a
    group of heads whose 3 x heads vectors fit one."""
    return (jnp.dtype(dtype) == jnp.dtype(F32) and dk % LANES == 0
            and dv % LANES == 0
            and linear_state_heads_per_step(heads) is not None)


def _kernel(layer_ref, fresh_ref, alive_ref, row_ref, group_ref, beta_ref,
            q_ref, k_ref, g_ref, v_ref, s_in, s_out, o_ref, *, heads: int,
            group: int):
    del layer_ref, group_ref            # read by the index maps
    b, hg = pl.program_id(0), pl.program_id(1)
    alive = alive_ref[b] != 0

    @pl.when(alive)
    def _():
        # the group's q | k | g as rows of one tile, then as columns
        rows = [q_ref[...], k_ref[...], g_ref[...]]
        pad = jnp.zeros((LANES - 3 * group, rows[0].shape[1]), F32)
        cols = jnp.concatenate([*rows, pad], axis=0).T          # [dk, 128]
        fresh = fresh_ref[b] != 0
        for j in range(group):
            q = cols[:, j:j + 1]
            k = cols[:, group + j:group + j + 1]
            g = cols[:, 2 * group + j:2 * group + j + 1]
            s = jnp.where(fresh, 0.0, s_in[j])
            decayed = s * jnp.exp(g)
            sk = jnp.sum(decayed * k, axis=0, keepdims=True)     # [1, dv]
            sq = jnp.sum(decayed * q, axis=0, keepdims=True)
            beta = beta_ref[b * heads + hg * group + j]
            u = beta * (v_ref[j:j + 1, :] - sk)
            kq = jnp.sum(k * q, axis=0, keepdims=True)           # [1, 1]
            o_ref[j:j + 1, :] = sq + kq * u
            s_out[j] = decayed + k * u

    @pl.when(jnp.logical_not(alive))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # a dead slot's steps name a live slot's block (``_resident``), which
    # they must leave as it is; with no slot alive there is none, and the
    # one block every step then names goes out as it came in
    @pl.when(alive_ref[row_ref[b]] == 0)
    def _():
        s_out[...] = s_in[...]


def _resident(alive: jax.Array, n_groups: int) -> tuple[jax.Array, jax.Array]:
    """Which block of matrices each slot's grid steps name, so that a slot
    that is not alive moves none: (row [B], group [B]), group < 0 meaning
    the step's own.  The pipeline neither fetches nor writes back a block
    whose index is the last step's, so a dead slot names the block in VMEM
    when its steps begin — the last group of the live slot before it — and
    the dead slots in front of the first live one name that one's first
    group, which is then fetched once, early."""
    at = jnp.arange(alive.shape[0], dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(alive, at, -1))
    first = jnp.argmax(alive).astype(jnp.int32)
    row = jnp.where(before >= 0, before, first)
    group = jnp.where(alive, -1, jnp.where(before >= 0, n_groups - 1, 0))
    return row, group.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"),
                   donate_argnums=(0,))
def state_update(state: jax.Array, layer: jax.Array, q: jax.Array,
                 k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                 fresh: jax.Array, alive: jax.Array,
                 heads_per_step: int | None = None,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """state [L, B, H, dk, dv] float32; layer scalar int32; q, k, g
    [B, H, dk]; v [B, H, dv]; beta [B, H]; fresh, alive [B] bool ->
    (o [B, H, dv] float32, state).  Row b of the dispatch is slot b."""
    _, b, h, dk, dv = state.shape
    group = heads_per_step or linear_state_heads_per_step(h)
    if group is None or h % group or 3 * group > LANES:
        raise ValueError(f"{h} heads in groups of {group}")
    row, named = _resident(alive, h // group)

    def vectors(i, j, *_):
        return (i, j, 0)

    def matrices(i, j, layer_ref, fresh_ref, alive_ref, row_ref, group_ref,
                 *_):
        own = group_ref[i] < 0
        return (layer_ref[0], row_ref[i],
                jnp.where(own, j, group_ref[i]), 0, 0)

    keys = pl.BlockSpec((None, group, dk), vectors)
    values = pl.BlockSpec((None, group, dv), vectors)
    tile = pl.BlockSpec((None, None, group, dk, dv), matrices)
    cost = linear_state_cost(b, h, dk, dv)
    state, o = pl.pallas_call(
        functools.partial(_kernel, heads=h, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(b, h // group),
            in_specs=[keys, keys, keys, values, tile],
            out_specs=[tile, values]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, h, dv), F32)],
        # operands: layer, fresh, alive, row, named, beta, q, k, g, v, state
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"], transcendentals=cost["transcendentals"],
            bytes_accessed=cost["hbm_bytes"]),
        interpret=interpret,
        name="linear_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fresh.astype(jnp.int32),
      alive.astype(jnp.int32), row, named, beta.astype(F32).reshape(b * h),
      q.astype(F32), k.astype(F32), g.astype(F32), v.astype(F32), state)
    return o, state
