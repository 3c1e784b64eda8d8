"""The decode step of the state-space recurrence, the state read once and
written once.

``ops/ssm_state.py::ssd_step`` is the arithmetic; under XLA on the TPU it is
two reads and one write of a layer's state (268 MB at 64 slots x 128 heads x
64 x 128 float32): one fusion recomputes the new state to read ``y`` out of
it, a second recomputes it to write it where it lies.  Here a head's matrix
waits in VMEM between the update and the read-out:

``state_update``  the whole leaf ``state`` [L, slots, H, P, N] with the
                  layer's row ``layer`` updated by one token a slot, and
                  ``y`` [slots, H, P] float32.  The leaf is donated and
                  aliased: the kernel writes the layer's row where it lies.

A grid step is one slot and ``ssm_state_heads_per_step`` heads of one group
of B and C: their matrices come in as one block, double buffered by the
pipeline, and leave as one.  The matrices lie P on the sublanes and N on the
lanes, so B and C are rows as they come, but a head's Δx is needed down the
*sublanes* and the read-out S'C sums along the *lanes*.  x and y therefore
travel as whole 128-lane rows — ``128 / P`` heads a row, the ``[slots, H, P]``
arrays seen as ``[slots, H·P / 128, 128]`` — and the step turns two tiles:
its x rows once, into columns; and the products S' ⊙ C of each row's heads,
so that their sum runs down the sublanes (adds of whole registers: a sum
along the lanes of every register holds the kernel to 270 GB/s, PERF.md §6,
PR 53) and comes out as the y row it is stored as.  Every product with the
state is a multiply and a sum on the vector unit: **no ``dot``** (Mosaic
multiplies float32 operands in one bf16 pass of the matrix unit, PERF.md §6,
PR 47; a state rounded to bf16 on every read drifts in this model, PR 52).

The slot mechanics are the delta rule's kernel's (ops/pallas/linear_state.py):
a ``fresh`` slot starts from zeros whatever it held; a slot that is not
``alive`` keeps its matrices bit for bit — they are not moved at all: its
grid steps name the block that is in VMEM already (``_resident``) — and its
``y`` is zero.  Both are per-slot scalars, prefetched, as are the decay
exp(Δ·A), Δ and D a head.  The grid runs in order ("arbitrary" on both axes):
the naming leans on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.linear_state import _resident
from dynamo_tpu.ops.pallas.registry import (
    ssm_state_cost,
    ssm_state_heads_per_step,
)

__all__ = ["state_update", "state_update_supported"]

F32 = jnp.float32
LANES = 128         # the vector unit's lanes: a row of x and y, a turned tile


def state_update_supported(heads: int, p: int, n: int, groups: int,
                           dtype) -> bool:
    """Whether the kernel takes this geometry: a float32 state whose matrices
    are whole (8, 128) tiles, heads of which a whole number fill a 128-lane
    row of x, and heads that share B and C in groups a grid step tiles."""
    return (jnp.dtype(dtype) == jnp.dtype(F32) and n % LANES == 0
            and ssm_state_heads_per_step(heads, groups, p, n) is not None)


def _kernel(layer_ref, fresh_ref, alive_ref, row_ref, group_ref, decay_ref,
            dt_ref, x_ref, d_ref, b_ref, c_ref, s_in, s_out, y_ref, *,
            heads: int, group: int, p: int):
    del layer_ref, group_ref            # read by the index maps
    i, hg = pl.program_id(0), pl.program_id(1)
    alive = alive_ref[i] != 0
    per = LANES // p                    # heads a row of x and y

    @pl.when(alive)
    def _():
        # the step's x rows as columns: head j's down the sublanes
        # (j % per) * p .. of column j // per
        rows = x_ref[...]
        cols = jnp.concatenate(
            [rows, jnp.zeros((LANES - rows.shape[0], LANES), F32)], axis=0).T
        fresh = fresh_ref[i] != 0
        b, c = b_ref[...], c_ref[...]                       # [1, N]
        read = []
        for j in range(group):
            at = i * heads + hg * group + j
            x = cols[j % per * p:(j % per + 1) * p, j // per:j // per + 1]
            s = jnp.where(fresh, 0.0, s_in[j])
            new = s * decay_ref[at] + (dt_ref[at] * x) * b
            s_out[j] = new
            read.append(new * c)
        for k in range(group // per):
            # [128 (head, P), N] turned: the sum over N leaves the y row
            tile = jnp.concatenate(read[per * k:per * (k + 1)], axis=0)
            y_ref[k:k + 1, :] = jnp.sum(tile.T, axis=0, keepdims=True)
        y_ref[...] += d_ref[...] * rows

    @pl.when(jnp.logical_not(alive))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # a dead slot's steps name a live slot's block (``_resident``), which
    # they must leave as it is; with no slot alive there is none, and the
    # one block every step then names goes out as it came in
    @pl.when(alive_ref[row_ref[i]] == 0)
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("heads_per_step", "interpret"),
                   donate_argnums=(0,))
def state_update(state: jax.Array, layer: jax.Array, x: jax.Array,
                 dt: jax.Array, a_head: jax.Array, b: jax.Array, c: jax.Array,
                 d: jax.Array, fresh: jax.Array, alive: jax.Array,
                 heads_per_step: int | None = None,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """state [L, B, H, P, N] float32; layer scalar int32; x [B, H, P]; dt
    [B, H] (the step Δ >= 0); a_head [H] (A < 0); b, c [B, G, N]; d [H];
    fresh, alive [B] bool -> (y [B, H, P] float32, state): ``ssd_step``'s
    arguments and results.  Row i of the dispatch is slot i."""
    _, rows, h, p, n = state.shape
    g = b.shape[1]
    group = heads_per_step or ssm_state_heads_per_step(h, g, p, n)
    if group is None or (h // g) % group or LANES % p or group * p % (8 * LANES):
        raise ValueError(f"{h} heads of {g} groups in steps of {group}")
    row, named = _resident(alive, h // group)
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a_head.astype(F32))
    wide = (h * p // LANES, LANES)      # x, y and D a row of 128 lanes

    def vectors(i, j, *_):
        return (i, j, 0)

    def shared(i, j, *_):               # the B and C of the step's heads
        return (i, j * group // (h // g), 0, 0)

    def matrices(i, j, layer_ref, fresh_ref, alive_ref, row_ref, group_ref,
                 *_):
        own = group_ref[i] < 0
        return (layer_ref[0], row_ref[i],
                jnp.where(own, j, group_ref[i]), 0, 0)

    xs = pl.BlockSpec((None, group * p // LANES, LANES), vectors)
    ds = pl.BlockSpec((group * p // LANES, LANES), lambda i, j, *_: (j, 0))
    bc = pl.BlockSpec((None, None, 1, n), shared)
    tile = pl.BlockSpec((None, None, group, p, n), matrices)
    cost = ssm_state_cost(rows, h, p, n, g)
    state, y = pl.pallas_call(
        functools.partial(_kernel, heads=h, group=group, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(rows, h // group),
            in_specs=[xs, ds, bc, bc, tile],
            out_specs=[tile, xs]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, *wide), F32)],
        # operands: layer, fresh, alive, row, named, decay, dt, x, d, b, c,
        # state
        input_output_aliases={11: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"], transcendentals=cost["transcendentals"],
            bytes_accessed=cost["hbm_bytes"]),
        interpret=interpret,
        name="ssm_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fresh.astype(jnp.int32),
      alive.astype(jnp.int32), row, named, decay.reshape(rows * h),
      dt.reshape(rows * h), x.astype(F32).reshape(rows, *wide),
      jnp.repeat(d.astype(F32), p).reshape(wide),
      b.astype(F32).reshape(rows, g, 1, n),
      c.astype(F32).reshape(rows, g, 1, n), state)
    return y.reshape(rows, h, p), state
