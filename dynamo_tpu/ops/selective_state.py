"""A layer whose memory is a state of fixed size: the selective state-space
recurrence with a decay a channel and state index (Mamba-1's selective scan,
arXiv:2312.00752, as Jamba's layers run it).

Held per engine slot beside the short convolution's tail, as the other two
recurrences' states are (ops/linear_state.py, ops/ssm_state.py,
docs/linear_state.md); the functions here are the arithmetic on arrays a
caller has already picked out of it.

The recurrence, a channel c of the layer's inner width, ``h`` in R^N float32
(N numbers a channel: no heads, no groups), Δ >= 0 the channel's step, A < 0
its N decay rates, B and C one N-vector a token shared by every channel:

    h_t[n, c] = exp(Δ_t[c] A[n, c]) h_{t-1}[n, c] + Δ_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n]            (+ D[c] x_t[c], the caller's)

The decay differs by channel *and* index, so a chunk has no matrix-product
form (ops/ssm_state.py's ``ssd_chunk`` leans on one decay a head): the work is
element-wise, N·channels exponentials a token.  ``selective_step`` is one
token a row: the CPU's path and the oracle of the kernel that a decode over
the slot array runs on the TPU.  ``selective_scan`` runs a dispatch's tokens
through it one at a time under ``lax.scan``: the CPU's path and the oracle of
the prefill kernel (ops/pallas/selective_state.py, chosen by ``step_impl`` /
``scan_impl``).  A token with Δ = 0 is an identity step: that is how padding
is written.

The channels are the trailing axes, whatever their number: the state lies
``[N, channels / 128, 128]`` a slot (N leading, the channels filling whole
vector registers), the functions take that or a flat ``[N, channels]`` alike.
Float32 throughout and no matrix product at all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.linear_state import kernel_gate

__all__ = ["selective_step", "selective_scan", "step_impl", "scan_impl"]

F32 = jnp.float32


def _geometry(n: int, rows: int, lanes: int, state_dtype) -> str:
    return f"{n} x {rows} x {lanes} {jnp.dtype(state_dtype)} a slot"


def step_impl(n: int, rows: int, lanes: int, state_dtype) -> tuple[str, str]:
    """``kernel_gate`` for one token a row over the slot array: on the TPU
    the kernel that updates a slot's state where it lies and reads ``y`` out
    of it while it is in VMEM, elsewhere — and for a geometry the kernel
    does not tile — ``selective_step``."""
    from dynamo_tpu.ops.pallas.selective_state import state_update_supported

    return kernel_gate(state_update_supported(n, rows, lanes, state_dtype),
                       _geometry(n, rows, lanes, state_dtype))


def scan_impl(n: int, rows: int, lanes: int, state_dtype) -> tuple[str, str]:
    """``kernel_gate`` for a prefill chunk: on the TPU the kernel that holds
    a tile of channels' state in registers across the chunk's tokens,
    elsewhere ``selective_scan``."""
    from dynamo_tpu.ops.pallas.selective_state import state_scan_supported

    return kernel_gate(state_scan_supported(n, rows, lanes, state_dtype),
                       _geometry(n, rows, lanes, state_dtype))


def _over_channels(t: jax.Array, like: jax.Array) -> jax.Array:
    """[..., N] -> [..., N, 1, ...]: one number an index, over the channel
    axes of ``like`` [..., N, *channels]."""
    return t.reshape(t.shape + (1,) * (like.ndim - t.ndim))


def selective_step(x, dt, a, b, c, state):
    """One token a row.  x, dt [B, *ch] (the step Δ >= 0); a [N, *ch]
    (A < 0); b, c [B, N]; state [B, N, *ch] float32 -> (y [B, *ch] float32
    without the D skip, state)."""
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    decay = jnp.exp(dt[:, None] * a.astype(F32))
    new = state * decay + (dt * x)[:, None] * _over_channels(b, state)
    return (new * _over_channels(c, state)).sum(axis=1), new


def selective_scan(x, dt, a, b, c, state):
    """``selective_step`` over the S tokens of a dispatch under ``lax.scan``.
    x, dt [B, S, *ch]; b, c [B, S, N]; state [B, N, *ch] float32 ->
    (y [B, S, *ch] float32, state after the S tokens)."""
    def one(st, xs):
        y, st = selective_step(*xs[:2], a, *xs[2:], st)
        return st, y

    state, y = jax.lax.scan(
        one, state, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state
