"""A layer whose memory is a state of fixed size: the gated delta rule with a
decay per key channel, and the short causal convolution in front of it.

No paged pool: what such a layer carries from one token to the next is one
matrix a head and the last ``K - 1`` inputs of the convolution, held **per
engine slot** (docs/linear_state.md).  The functions here are the layer's
arithmetic on arrays a caller has already picked out of that state.

The recurrence, a head, ``S`` in R^{dk x dv} float32 (``g`` = log decay <= 0):

    S' = Diag(exp g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;        o_t = S_t^T q_t

``delta_rule_step`` is that, one token a row.  ``delta_rule_chunk`` is the
same map for C tokens of one sequence at once (the WY form: the C rank-one
updates are one triangular system), and ``delta_rule_scan`` runs a dispatch's
tokens through it C at a time.  A token with g = 0 and beta = 0 is an
identity step in all of them: that is how padding is written.

Everything is float32 and no product goes through a single bf16 pass of the
matrix unit: matrix-vector products with the state are multiply-and-sum on the
vector unit, matrix products ask for ``Precision.HIGHEST``.  They are a small
share of a layer's arithmetic (docs/linear_state.md has the count), and a
state rounded to bf16 on every read is a different model.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

__all__ = ["CHUNK", "delta_rule_step", "delta_rule_chunk", "delta_rule_scan",
           "short_conv", "grouped_conv", "carried_tail", "init_state",
           "unit_lower_inverse", "step_impl", "kernel_gate"]

# tokens a chunk.  The decay is per channel, so the chunk's two score
# matrices are sums over [C, C, dk] (no product of two [C, dk] factors gives
# exp(G_r - G_j) without forming 1/exp(G_j), which overflows under strong
# decay): 2·C·dk exponentials a token and head.  64 keeps the sequential
# part of a 512-token dispatch at 8 steps a layer; at 128 the [C, C, dk]
# sums cost more than the 4 steps they save
CHUNK = 64
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """a [..., m, n] @ b [..., n, p] in float32.  A contraction shorter than
    a tile of the matrix unit is a multiply-and-sum."""
    if a.shape[-1] < 16:
        return (a[..., :, :, None] * b[..., None, :, :]).sum(axis=-2)
    return jnp.matmul(a, b, precision=_HI)


def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + A)^-1 for ``a`` [..., C, C] of which only the strictly lower
    triangle is read; C a power of two.

    By halves: inv [[L11, 0], [L21, L22]] = [[T11, 0], [-T22 L21 T11, T22]],
    from 1x1 blocks (their inverse is 1) up, every block of a level at once:
    log2(C) levels of two small matrix products in place of C sequential
    rows.  Every intermediate is a block of the inverse of a leading
    sub-matrix, so it is as well conditioned as forward substitution (a
    product of (I + A^(2^i)) is not: with like keys A's powers grow)."""
    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"chunk of {c} tokens: not a power of two")
    lead = a.shape[:-2]
    t = jnp.ones((*lead, c, 1, 1), F32)          # the C 1x1 diagonal blocks
    n = 1
    while n < c:
        nb = c // (2 * n)
        # the (2i+1, 2i) block of size n for every pair i
        blocks = a.reshape(*lead, nb, 2, n, nb, 2, n)
        pick = jnp.arange(nb)
        l21 = jnp.moveaxis(blocks[..., pick, 1, :, pick, 0, :], 0, -3)
        pairs = t.reshape(*lead, nb, 2, n, n)
        t11, t22 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        low = -_mm(_mm(t22, l21), t11)
        top = jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1)
        bottom = jnp.concatenate([low, t22], axis=-1)
        t = jnp.concatenate([top, bottom], axis=-2)   # [..., nb, 2n, 2n]
        n *= 2
    return t[..., 0, :, :]


def kernel_gate(tiles: bool, geometry: str) -> tuple[str, str]:
    """``("pallas" | "xla", why)`` for a decode step's state update, either
    recurrence's: the kernel on the TPU where it ``tiles`` the state, the
    XLA form elsewhere.  A static function of the environment, the backend
    and the shapes, asked before tracing."""
    if os.environ.get("DYNAMO_DISABLE_PALLAS"):
        return "xla", "DYNAMO_DISABLE_PALLAS is set"
    backend = jax.default_backend()
    if backend != "tpu":
        return "xla", f"backend is {backend}"
    if not tiles:
        return "xla", f"{geometry} do not tile"
    return "pallas", "tpu"


def step_impl(heads: int, dk: int, dv: int, state_dtype) -> tuple[str, str]:
    """``kernel_gate`` for one token a row over the slot array: on the TPU
    the kernel that holds a head's matrix in VMEM between the products and
    the update (ops/pallas/linear_state.py), elsewhere — and for a geometry
    the kernel does not tile — ``delta_rule_step``."""
    from dynamo_tpu.ops.pallas.linear_state import state_update_supported

    return kernel_gate(
        state_update_supported(heads, dk, dv, state_dtype),
        f"{heads} heads of {dk} x {dv} {jnp.dtype(state_dtype)}")


def delta_rule_step(q, k, v, g, beta, state):
    """One token a row.  q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H];
    state [B, H, dk, dv] float32 -> (o [B, H, dv], state).  The CPU's path
    and the kernel's oracle: under XLA the state is read twice and written
    once, S'^T k and S'^T q in one pass (o = S'^T q + (k·q) u), the update
    in a second."""
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    decayed = state * jnp.exp(g)[..., None]
    sk = (decayed * k[..., None]).sum(axis=-2)
    sq = (decayed * q[..., None]).sum(axis=-2)
    u = beta[..., None] * (v - sk)
    o = sq + (k * q).sum(axis=-1, keepdims=True) * u
    return o, decayed + k[..., None] * u[..., None, :]


def delta_rule_chunk(q, k, v, g, beta, state):
    """C tokens of one sequence a row.  q, k, g [B, C, H, dk]; v
    [B, C, H, dv]; beta [B, C, H]; state [B, H, dk, dv] float32 ->
    (o [B, C, H, dv], state after the C tokens).

    With G_r = sum_{i<=r} g_i:  A_rj = beta_r (k_r ⊙ exp(G_r - G_j))·k_j
    (j < r);  U = (I + A)^-1 Diag(beta) (V - (K ⊙ exp G) S0);
    o_r = S0^T (q_r ⊙ exp G_r) + sum_{j<=r} ((k_j ⊙ exp(G_r - G_j))·q_r) u_j;
    S_C = Diag(exp G_C) S0 + sum_j (k_j ⊙ exp(G_C - G_j)) u_j^T.
    Every exponent is <= 0."""
    q, k, v, g = (jnp.moveaxis(x.astype(F32), 1, 2) for x in (q, k, v, g))
    beta = jnp.moveaxis(beta.astype(F32), 1, 2)              # [B, H, C]
    c = q.shape[2]
    cum = jnp.cumsum(g, axis=2)                              # G [B, H, C, dk]
    at = jnp.arange(c)
    # exp(G_r - G_j) where j <= r, 0 elsewhere; only ever summed over dk
    gap = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    decay = jnp.exp(jnp.where((at[:, None] >= at[None, :])[..., None],
                              gap, -jnp.inf))                # [B, H, C, C, dk]
    kk = (k[:, :, :, None, :] * decay * k[:, :, None, :, :]).sum(axis=-1)
    qk = (q[:, :, :, None, :] * decay * k[:, :, None, :, :]).sum(axis=-1)
    a = jnp.where(at[:, None] > at[None, :], kk * beta[..., None], 0.0)
    t = unit_lower_inverse(a)
    through = jnp.exp(cum)                                   # exp G_r
    rhs = beta[..., None] * (v - _mm(k * through, state))
    u = _mm(t, rhs)                                          # [B, H, C, dv]
    o = _mm(q * through, state) + _mm(qk, u)
    left = jnp.exp(cum[:, :, -1:, :] - cum)                  # exp(G_C - G_j)
    new = (state * through[:, :, -1, :, None]
           + _mm(jnp.swapaxes(k * left, -1, -2), u))
    return jnp.moveaxis(o, 2, 1), new


def delta_rule_scan(q, k, v, g, beta, state, chunk: int = CHUNK):
    """``delta_rule_chunk`` over the S tokens of a dispatch, ``chunk`` at a
    time under ``lax.scan``.  Shapes as there with S in place of C.  An S
    that is not whole chunks (or, below a chunk, not a power of two) is
    padded with identity steps; the engine's prefill buckets never are."""
    s = q.shape[1]
    whole = (1 << (s - 1).bit_length()) if s <= chunk else -(-s // chunk) * chunk
    if whole != s:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, whole - s)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    if whole <= chunk:
        o, state = delta_rule_chunk(q, k, v, g, beta, state)
        return o[:, :s], state

    def pieces(x):                   # [B, S, ...] -> [S/C, B, C, ...]
        return jnp.moveaxis(
            x.reshape(x.shape[0], whole // chunk, chunk, *x.shape[2:]), 1, 0)

    def one(st, xs):
        o, st = delta_rule_chunk(*xs, st)
        return st, o

    state, o = jax.lax.scan(one, state, tuple(
        pieces(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)
    return o.reshape(o.shape[0], whole, *o.shape[3:])[:, :s], state


def carried_tail(xx, n_real, width: int, dtype):
    """What a causal operation over time carries to the next dispatch: of
    xx = tail ‖ x [B, K-1+S, D] (x real tokens first) the ``width`` = K-1
    rows before position ``n_real`` [B] of x, in ``dtype``.  With n_real 0
    that is the old tail as it was."""
    new = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
        row, n, width, axis=0))(xx, n_real)
    return new.astype(dtype)


def short_conv(x, w, tail, n_real, bias=None):
    """Causal depth-wise convolution over time.  x [B, S, D] (this
    dispatch's inputs, real tokens first), w [D, K], tail [B, K-1, D] (the
    K-1 inputs before x[:, 0]; zeros at a sequence's start), n_real [B]
    (how many of the S are real), bias [D] or None -> (y [B, S, D] float32
    with y_t = sum_i w[:, i] ⊙ xx_{t+i} (+ bias), xx = tail ‖ x; the new
    tail: the K-1 inputs before position n_real, in ``tail``'s type).  With
    n_real 0 the tail comes back as it was."""
    kk = w.shape[1]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    s = x.shape[1]
    wf = w.astype(F32)
    y = sum(xx[:, i:i + s].astype(F32) * wf[:, i] for i in range(kk))
    if bias is not None:
        y = y + bias.astype(F32)
    return y, carried_tail(xx, n_real, kk - 1, tail.dtype)


def grouped_conv(x, w, tail, n_real, bias=None):
    """Causal convolution over time that mixes the channels of a group (a
    head): x [B, S, G·Di], w [K, G, Di, Do] (a Di x Do matrix a group and
    tap), tail [B, K-1, G·Di], n_real [B], bias [G·Do] or None -> (y
    [B, S, G·Do] float32 with y_t[g] = sum_i xx_{t+i}[g] w[i, g] (+ bias),
    xx = tail ‖ x; the new tail, as ``short_conv``'s).  K·G small matrix
    products on the matrix unit, summed in float32; ``short_conv`` is its
    depth-wise sibling."""
    kk, g, di, do = w.shape
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    b, s = x.shape[:2]
    xg = xx.reshape(b, kk - 1 + s, g, di)
    y = sum(jnp.einsum("bsgi,gio->bsgo", xg[:, i:i + s], w[i],
                       preferred_element_type=F32) for i in range(kk))
    y = y.reshape(b, s, g * do)
    if bias is not None:
        y = y + bias.astype(F32)
    return y, carried_tail(xx, n_real, kk - 1, tail.dtype)


def init_state(layers: int, slots: int, heads: int, dk: int, dv: int,
               conv_width: int, conv_kernel: int, conv_dtype=jnp.bfloat16,
               state_dtype=F32) -> dict:
    """The leaves a stack of ``layers`` such layers keeps for ``slots``
    sequences: ``state`` [L, slots, H, dk, dv] (float32 unless a test asks
    otherwise), ``conv`` [L, slots, K-1, D] and ``state_pos`` [slots], the
    position after the last token each slot's state has taken in."""
    return {
        "state": jnp.zeros((layers, slots, heads, dk, dv), state_dtype),
        "conv": jnp.zeros((layers, slots, conv_kernel - 1, conv_width),
                          conv_dtype),
        "state_pos": jnp.zeros((slots,), jnp.int32),
    }
