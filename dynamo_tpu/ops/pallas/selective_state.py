"""The selective state-space recurrence's two kernels: the decode step over the
slot array and the scan of a prefill chunk, the state read once and written
once in both.

``ops/selective_state.py`` is the arithmetic.  A slot's state in one layer is
``[N, R, 128]`` float32: N numbers a channel, the channels filling R rows of
128 lanes — N leading, so that ``h[n]`` is R/8 whole vector registers and the
token's B_t[n] and C_t[n], one number for every channel, are *scalars* (read
from SMEM and multiplied into a register; laid N on the lanes the state would
fill an eighth of each register and B and C would need a lane broadcast a
token).  There is no product with a matrix and no sum across lanes or
sublanes anywhere: ``y`` is N multiply-adds of whole registers.

``state_update``  the whole leaf ``state`` [L, slots, N, R, 128] with the
                  layer's row ``layer`` advanced by one token a slot, and
                  ``y`` [slots, R, 128] float32 (without the D skip, which
                  the caller adds).  A grid step is one slot: its 16 x R x
                  128 block comes in, double buffered by the pipeline, and
                  leaves.  A ``fresh`` slot starts from zeros whatever it
                  held; a slot that is not ``alive`` keeps its state bit for
                  bit — it is not moved at all: its grid step names the
                  block that is in VMEM already (``_resident``) — and its
                  ``y`` is zero.
``state_scan``    the same leaf with the slots of a prefill dispatch's rows
                  advanced by the S tokens of each row's chunk, and ``y``
                  [B, S, R, 128].  The grid is (row, channel tile of 8
                  register rows, block of ``SELECTIVE_SCAN_TOKENS`` tokens):
                  a tile's state — N registers — is carried in registers
                  through the tokens of a block and waits in the output block
                  (VMEM) between blocks; exp(Δ A), the update and the
                  read-out are formed a token at a time and never reach HBM.
                  Reads x̂, Δ (once a tile), B, C (SMEM, once a call), writes
                  y, and the state once in and once out.

Both alias the donated leaf: the kernel writes the layer's rows where they
lie.  Δ = 0 is an identity step (padding).  Two rows of one ``state_scan``
call must not name one slot: the pipeline would fetch the second's state
before the first's is written back (the engine's prefill dispatch is one
request's chunk; the XLA form has the same rule through ``.at[].set``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.linear_state import _resident
from dynamo_tpu.ops.pallas.registry import (
    SELECTIVE_SCAN_ROWS_PER_TILE,
    SELECTIVE_SCAN_SCALARS,
    SELECTIVE_SCAN_TOKENS,
    selective_scan_cost,
    selective_step_cost,
)

__all__ = ["state_update", "state_update_supported", "state_scan",
           "state_scan_supported"]

F32 = jnp.float32
LANES = 128


def state_update_supported(n: int, rows: int, lanes: int, dtype) -> bool:
    """Whether the decode kernel takes this geometry: a float32 state whose
    channels fill whole (8, 128) registers."""
    return (jnp.dtype(dtype) == jnp.dtype(F32) and lanes == LANES
            and rows % 8 == 0)


def state_scan_supported(n: int, rows: int, lanes: int, dtype) -> bool:
    """Whether the prefill kernel takes this geometry: as the decode
    kernel's, in channel tiles of ``SELECTIVE_SCAN_ROWS_PER_TILE`` rows."""
    return (state_update_supported(n, rows, lanes, dtype)
            and rows % SELECTIVE_SCAN_ROWS_PER_TILE == 0)


# ------------------------------------------------------------ decode step


def _step_kernel(layer_ref, fresh_ref, alive_ref, row_ref, b_ref, c_ref,
                 x_ref, dt_ref, a_ref, s_in, s_out, y_ref, *, n: int):
    del layer_ref                       # read by the index map
    i = pl.program_id(0)
    alive = alive_ref[i] != 0

    @pl.when(alive)
    def _():
        fresh = fresh_ref[i] != 0
        dt = dt_ref[...]
        dtx = dt * x_ref[...]
        y = jnp.zeros_like(dt)
        for k in range(n):
            s = jnp.where(fresh, 0.0, s_in[k])
            new = s * jnp.exp(dt * a_ref[k]) + dtx * b_ref[i * n + k]
            s_out[k] = new
            y = y + new * c_ref[i * n + k]
        y_ref[...] = y

    @pl.when(jnp.logical_not(alive))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    # a dead slot's step names a live slot's block (``_resident``), which it
    # must leave as it is; with no slot alive there is none, and the one
    # block every step then names goes out as it came in
    @pl.when(alive_ref[row_ref[i]] == 0)
    def _():
        s_out[...] = s_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def state_update(state: jax.Array, layer: jax.Array, x: jax.Array,
                 dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                 fresh: jax.Array, alive: jax.Array,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """state [L, B, N, R, 128] float32; layer scalar int32; x, dt [B, R, 128]
    (the step Δ >= 0); a [N, R, 128] (A < 0); b, c [B, N]; fresh, alive [B]
    bool -> (y [B, R, 128] float32, state): ``selective_step``'s arguments
    and results less the D skip.  Row i of the dispatch is slot i."""
    _, slots, n, rows, lanes = state.shape
    if not state_update_supported(n, rows, lanes, state.dtype):
        raise ValueError(f"a state of {state.shape[2:]} {state.dtype} a slot")
    row, _ = _resident(alive, 1)

    def vectors(i, *_):
        return (i, 0, 0)

    def matrices(i, layer_ref, fresh_ref, alive_ref, row_ref, *_):
        return (layer_ref[0], row_ref[i], 0, 0, 0)

    vec = pl.BlockSpec((None, rows, lanes), vectors)
    tile = pl.BlockSpec((None, None, n, rows, lanes), matrices)
    cost = selective_step_cost(slots, n, rows * lanes)
    state, y = pl.pallas_call(
        functools.partial(_step_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(slots,),
            in_specs=[vec, vec,
                      pl.BlockSpec((n, rows, lanes), lambda i, *_: (0, 0, 0)),
                      tile],
            out_specs=[tile, vec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, rows, lanes), F32)],
        # operands: layer, fresh, alive, row, b, c, x, dt, a, state
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"], transcendentals=cost["transcendentals"],
            bytes_accessed=cost["hbm_bytes"]),
        interpret=interpret,
        name="selective_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fresh.astype(jnp.int32),
      alive.astype(jnp.int32), row, b.astype(F32).reshape(slots * n),
      c.astype(F32).reshape(slots * n), x.astype(F32), dt.astype(F32),
      a.astype(F32), state)
    return y, state


# ----------------------------------------------------------- prefill scan


def _scan_kernel(layer_ref, slot_ref, fresh_ref, b_ref, c_ref, x_ref, dt_ref,
                 a_ref, s_in, s_out, y_ref, *, n: int, s: int, ts: int):
    del layer_ref, slot_ref             # read by the index maps
    row, tb = pl.program_id(0), pl.program_id(2)

    # the tile's state waits in the output block between token blocks
    @pl.when(tb == 0)
    def _():
        s_out[...] = jnp.where(fresh_ref[row] != 0, 0.0, s_in[...])

    a = [a_ref[k] for k in range(n)]
    first = (row * s + tb * ts) * n

    def token(i, h):
        dt = dt_ref[i]
        dtx = dt * x_ref[i]
        at = first + i * n
        y = jnp.zeros_like(dt)
        new = []
        for k in range(n):
            hk = h[k] * jnp.exp(dt * a[k]) + dtx * b_ref[at + k]
            y = y + hk * c_ref[at + k]
            new.append(hk)
        y_ref[i] = y
        return tuple(new)

    h = jax.lax.fori_loop(0, ts, token, tuple(s_out[k] for k in range(n)))
    for k in range(n):
        s_out[k] = h[k]


def _scan_call(state, layer, slots, x, dt, a, b, c, fresh, interpret):
    _, _, n, rows, lanes = state.shape
    nb, s = x.shape[:2]
    ts = next(t for t in (SELECTIVE_SCAN_TOKENS, 64, 32, 16, 8, 4, 2, 1)
              if s % t == 0)
    tr = SELECTIVE_SCAN_ROWS_PER_TILE

    def tokens(i, j, t, *_):
        return (i, t, j, 0)

    def matrices(i, j, t, layer_ref, slot_ref, *_):
        return (layer_ref[0], slot_ref[i], 0, j, 0)

    tok = pl.BlockSpec((None, ts, tr, lanes), tokens)
    tile = pl.BlockSpec((None, None, n, tr, lanes), matrices)
    cost = selective_scan_cost(nb, s, n, rows * lanes)
    state, y = pl.pallas_call(
        functools.partial(_scan_kernel, n=n, s=s, ts=ts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(nb, rows // tr, s // ts),
            in_specs=[tok, tok,
                      pl.BlockSpec((n, tr, lanes),
                                   lambda i, j, t, *_: (0, j, 0)),
                      tile],
            out_specs=[tile, tok]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nb, s, rows, lanes), F32)],
        # operands: layer, slots, fresh, b, c, x, dt, a, state
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"], transcendentals=cost["transcendentals"],
            bytes_accessed=cost["hbm_bytes"]),
        interpret=interpret,
        name="selective_state_scan",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), b.astype(F32).reshape(nb * s * n),
      c.astype(F32).reshape(nb * s * n), x.astype(F32), dt.astype(F32),
      a.astype(F32), state)
    return y, state


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def state_scan(state: jax.Array, layer: jax.Array, slots: jax.Array,
               x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, fresh: jax.Array,
               interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """state [L, slots, N, R, 128] float32; layer scalar int32; slots [B]
    (the slot of each row, no two alike); x, dt [B, S, R, 128] (the step
    Δ >= 0, 0 for padding); a [N, R, 128] (A < 0); b, c [B, S, N]; fresh [B]
    bool -> (y [B, S, R, 128] float32, state): ``selective_scan``'s arguments
    and results less the D skip.  A row with no real token (Δ = 0
    throughout) leaves its slot as it was.  B and C travel as scalars: a call
    takes ``SELECTIVE_SCAN_SCALARS`` numbers of each, a longer chunk goes
    through in pieces."""
    _, _, n, rows, lanes = state.shape
    if not state_scan_supported(n, rows, lanes, state.dtype):
        raise ValueError(f"a state of {state.shape[2:]} {state.dtype} a slot")
    nb, s = x.shape[:2]
    piece = max(8, SELECTIVE_SCAN_SCALARS // (nb * n) // 8 * 8)
    ys = []
    for at in range(0, s, piece):
        to = min(at + piece, s)
        y, state = _scan_call(
            state, layer, slots, x[:, at:to], dt[:, at:to], a, b[:, at:to],
            c[:, at:to], fresh if at == 0 else jnp.zeros_like(fresh),
            interpret)
        ys.append(y)
    return (ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)), state
