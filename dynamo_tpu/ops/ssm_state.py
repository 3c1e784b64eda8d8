"""A layer whose memory is a state of fixed size: the state-space recurrence
with a scalar decay a head (Mamba-2's SSD, arXiv:2405.21060).

Held per engine slot beside the short convolution's tail, as the delta rule's
state is (ops/linear_state.py, docs/linear_state.md); the functions here are
the arithmetic on arrays a caller has already picked out of it.

The recurrence, a head, ``S`` in R^{P x N} float32, ``a`` = Δ·A <= 0 the log
decay (one number a head and token), B and C shared by the heads of a group:

    S_t = exp(a_t) S_{t-1} + (Δ_t x_t) ⊗ B_t;    y_t = S_t C_t + D ⊙ x_t

``ssd_step`` is that, one token a row: the CPU's path and the oracle of the
kernel that a decode over the slot array runs on the TPU
(ops/pallas/ssm_state.py, chosen by ``step_impl``).  ``ssd_chunk`` is the
same map for Q tokens of one sequence at once — with G_i = sum_{k<=i} a_k,

    y_i = sum_{j<=i} exp(G_i - G_j) (C_i·B_j) Δ_j x_j + exp(G_i) S_in C_i,
    S_out = exp(G_Q) S_in + sum_j exp(G_Q - G_j) (Δ_j x_j) ⊗ B_j

every exponent <= 0, all plain matrix products — and ``ssd_scan`` runs a
dispatch's tokens through it ``chunk`` at a time.  A token with Δ = 0 is an
identity step in all of them: that is how padding is written.

Float32 throughout, matrix products at ``Precision.HIGHEST`` (a state rounded
to bf16 on every read is another model: ops/linear_state.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.ops.linear_state import kernel_gate

__all__ = ["ssd_step", "ssd_chunk", "ssd_scan", "step_impl"]

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _heads(t: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def step_impl(heads: int, p: int, n: int, groups: int,
              state_dtype) -> tuple[str, str]:
    """``kernel_gate`` for one token a row over the slot array: on the TPU
    the kernel that reads ``y`` out of a head's matrix while it is in VMEM
    (ops/pallas/ssm_state.py), elsewhere — and for a geometry the kernel
    does not tile — ``ssd_step``."""
    from dynamo_tpu.ops.pallas.ssm_state import state_update_supported

    return kernel_gate(
        state_update_supported(heads, p, n, groups, state_dtype),
        f"{heads} heads of {p} x {n} {jnp.dtype(state_dtype)} in {groups} "
        "groups")


def ssd_step(x, dt, a_head, b, c, d, state):
    """One token a row.  x [B, H, P]; dt [B, H] (the step Δ >= 0); a_head
    [H] (A < 0); b, c [B, G, N]; d [H]; state [B, H, P, N] float32 ->
    (y [B, H, P], state).  The CPU's path and the kernel's oracle: under
    XLA on the TPU, between a slice of the slot array and its set, the state
    is read twice and written once — one fusion makes the new state to read
    ``y`` out of it, a second makes it again to write it (PERF.md §6,
    PR 52)."""
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    h = x.shape[1]
    decay = jnp.exp(dt * a_head.astype(F32))
    new = (state * decay[..., None, None]
           + (dt[..., None] * x)[..., None] * _heads(b, h)[..., None, :])
    y = (new * _heads(c, h)[..., None, :]).sum(axis=-1)
    return y + d.astype(F32)[:, None] * x, new


def ssd_chunk(x, dt, a_head, b, c, d, state):
    """Q tokens of one sequence a row.  x [B, Q, H, P]; dt [B, Q, H]; b, c
    [B, Q, G, N]; state [B, H, P, N] float32 -> (y [B, Q, H, P], state after
    the Q tokens)."""
    x, dt, b, c = (t.astype(F32) for t in (x, dt, b, c))
    q, h = x.shape[1], x.shape[2]
    g = b.shape[2]
    cum = jnp.cumsum(dt * a_head.astype(F32), axis=1)        # G [B, Q, H]
    at = jnp.arange(q)
    gap = cum[:, :, None, :] - cum[:, None, :, :]            # G_i - G_j
    within = jnp.exp(jnp.where((at[:, None] >= at[None, :])[..., None],
                               gap, -jnp.inf))               # [B, Q, Q, H]
    # C_i·B_j once a group, not a head
    cb = jnp.einsum("bign,bjgn->bijg", c, b, precision=_HI)
    dtx = dt[..., None] * x                                  # [B, Q, H, P]
    scores = within * jnp.repeat(cb, h // g, axis=-1)
    y = jnp.einsum("bijh,bjhp->bihp", scores, dtx, precision=_HI)
    ch, bh = _heads(c, h), _heads(b, h)                      # [B, Q, H, N]
    y += jnp.exp(cum)[..., None] * jnp.einsum(
        "bihn,bhpn->bihp", ch, state, precision=_HI)
    left = jnp.exp(cum[:, -1:, :] - cum)                     # exp(G_Q - G_j)
    new = (state * jnp.exp(cum[:, -1, :])[..., None, None]
           + jnp.einsum("bjhp,bjhn->bhpn", dtx * left[..., None], bh,
                        precision=_HI))
    return y + d.astype(F32)[:, None] * x, new


def ssd_scan(x, dt, a_head, b, c, d, state, chunk: int):
    """``ssd_chunk`` over the S tokens of a dispatch, ``chunk`` at a time
    under ``lax.scan``.  Shapes as there with S in place of Q.  An S above a
    chunk that is not whole chunks is padded with identity steps."""
    s = x.shape[1]
    if s <= chunk:
        return ssd_chunk(x, dt, a_head, b, c, d, state)
    whole = -(-s // chunk) * chunk
    if whole != s:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, whole - s)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))

    def pieces(t):                   # [B, S, ...] -> [S/Q, B, Q, ...]
        return jnp.moveaxis(
            t.reshape(t.shape[0], whole // chunk, chunk, *t.shape[2:]), 1, 0)

    def one(st, xs):
        xq, dtq, bq, cq = xs
        y, st = ssd_chunk(xq, dtq, a_head, bq, cq, d, st)
        return st, y

    state, y = jax.lax.scan(one, state, tuple(pieces(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape(y.shape[0], whole, *y.shape[3:])[:, :s], state
