"""ops/linear_state.py: the chunked (WY) form of the gated delta rule against
the recurrence one token at a time, the triangular inverse by halves, the
short convolution's carried tail, and the identity step that padding is."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import linear_state as ls

B, H, D = 2, 3, 16


def recurrence(q, k, v, g, beta, state):
    """The literal recurrence in float64: S' = Diag(e^g) S;  u = beta (v -
    S'^T k);  S = S' + k u^T;  o = S^T q."""
    q, k, v, g, beta, state = (np.asarray(x, np.float64)
                               for x in (q, k, v, g, beta, state))
    out = np.zeros_like(v)
    for b in range(q.shape[0]):
        for h in range(q.shape[2]):
            s = state[b, h].copy()
            for t in range(q.shape[1]):
                s = np.exp(g[b, t, h])[:, None] * s
                u = beta[b, t, h] * (v[b, t, h] - s.T @ k[b, t, h])
                s = s + np.outer(k[b, t, h], u)
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


def draw(t, decay, beta_shift, seed=0, like_keys=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, t, H, D))) * D ** -0.5
    k = jax.random.normal(ks[1], (B, t, H, D))
    if like_keys:       # consecutive keys nearly equal: A's entries near beta
        k = k[:, :1] + 0.05 * k
    v = jax.random.normal(ks[2], (B, t, H, D))
    g = -decay * jax.random.uniform(ks[3], (B, t, H, D))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, t, H)) + beta_shift)
    return q, unit(k), v, g, beta, jax.random.normal(ks[5], (B, H, D, D))


@pytest.mark.parametrize("t,chunk,decay,beta_shift,like_keys", [
    (64, 16, 0.5, 0.0, False),       # four chunks of 16
    (128, 64, 0.5, 0.0, False),      # a chunk boundary inside the sequence
    (64, 64, 50.0, 0.0, False),      # alpha near 0: exp(G_r - G_j) underflows
    (64, 16, 1e-4, 0.0, False),      # alpha near 1: nothing is forgotten
    (128, 64, 0.05, 5.0, False),     # beta near 2
    (128, 64, 0.05, 5.0, True),      # ... with like keys: I + A far from I
    (48, 16, 0.5, 0.0, False),       # three chunks
    (32, 64, 0.5, 0.0, False),       # shorter than a chunk: one of 32
    (75, 32, 0.5, 0.0, False),       # not whole chunks: padded with identity
    (20, 64, 0.5, 0.0, False),       # ... nor a power of two
], ids=["c16", "c64-boundary", "strong-decay", "no-decay", "beta-2",
        "beta-2-like-keys", "three-chunks", "short", "ragged-75", "ragged-20"])
def test_chunked_form_is_the_token_recurrence(t, chunk, decay, beta_shift,
                                              like_keys):
    args = draw(t, decay, beta_shift, like_keys=like_keys)
    want_o, want_s = recurrence(*args)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = ls.delta_rule_scan(*args, chunk=chunk)
        step_s, step_o = args[-1], []
        for i in range(t):
            o, step_s = ls.delta_rule_step(
                *(x[:, i] for x in args[:-1]), step_s)
            step_o.append(o)
    scale = max(1.0, np.abs(want_s).max())
    assert np.abs(np.stack(step_o, 1) - want_o).max() < 2e-5 * scale
    assert np.abs(step_s - want_s).max() < 2e-5 * scale
    assert np.abs(got_o - want_o).max() < 1e-4 * scale
    assert np.abs(got_s - want_s).max() < 1e-4 * scale


@pytest.mark.parametrize("c", [1, 2, 16, 64])
def test_unit_lower_inverse_is_the_inverse(c):
    rng = np.random.default_rng(c)
    a = np.tril(rng.normal(size=(2, 3, c, c)), -1).astype(np.float32)
    a[0, 0] = 2.0 * np.tril(np.ones((c, c)), -1)   # every key alike, beta 2
    with jax.default_matmul_precision("highest"):
        # what lies on and above the diagonal is not read
        got = np.asarray(ls.unit_lower_inverse(
            jnp.asarray(a + np.triu(np.full((c, c), 7.0, np.float32)))))
    want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
    assert np.abs(got - want).max() <= 1e-4 * max(1.0, np.abs(want).max())
    assert np.abs(got[0, 0]).max() <= 2.0 + 1e-5    # +-2, 1: no blow-up


def test_identity_step_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, state = draw(16, 0.5, 0.0)
    zero_g, zero_b = jnp.zeros_like(g), jnp.zeros_like(beta)
    _, after = ls.delta_rule_scan(q, k, v, zero_g, zero_b, state)
    assert np.array_equal(np.asarray(after), np.asarray(state))
    _, after = ls.delta_rule_step(q[:, 0], k[:, 0], v[:, 0], zero_g[:, 0],
                                  zero_b[:, 0], state)
    assert np.array_equal(np.asarray(after), np.asarray(state))
    # padding behind real tokens: the state is the real tokens' alone
    pad = jnp.arange(16) >= 10
    gp = jnp.where(pad[None, :, None, None], 0.0, g)
    bp = jnp.where(pad[None, :, None], 0.0, beta)
    _, padded = ls.delta_rule_scan(q, k, v, gp, bp, state)
    _, real = ls.delta_rule_scan(*(x[:, :10] for x in (q, k, v, g, beta)),
                                 state, chunk=2)
    assert np.abs(padded - real).max() < 1e-5


def test_short_conv_carries_its_tail_over_a_chunk_boundary():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 24, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    zeros = jnp.zeros((2, 3, 8), jnp.float32)
    whole, tail = ls.short_conv(x, w, zeros, jnp.asarray([24, 24]))
    # by hand: y_t = sum_i w_i x_{t-3+i}, zeros before the first token
    padded = np.concatenate([np.zeros((2, 3, 8)), np.asarray(x)], axis=1)
    want = sum(padded[:, i:i + 24] * np.asarray(w)[:, i] for i in range(4))
    assert np.abs(whole - want).max() < 1e-5
    assert np.array_equal(np.asarray(tail), np.asarray(x[:, -3:]))
    # 10 tokens, then 14 of which row 1 has only 9 real (5 of padding), then
    # one token a row: the same outputs, and the tail ends at the last real
    a, t1 = ls.short_conv(x[:, :10], w, zeros, jnp.asarray([10, 10]))
    b, t2 = ls.short_conv(x[:, 10:], w, t1, jnp.asarray([14, 9]))
    assert np.abs(jnp.concatenate([a, b], 1) - whole)[0].max() < 1e-5
    assert np.abs(jnp.concatenate([a, b], 1) - whole)[1, :19].max() < 1e-5
    assert np.array_equal(np.asarray(t2[0]), np.asarray(x[0, 21:24]))
    assert np.array_equal(np.asarray(t2[1]), np.asarray(x[1, 16:19]))
    c, t3 = ls.short_conv(x[:, 19:20], w, t2, jnp.asarray([0, 1]))
    assert np.abs(c[1, 0] - whole[1, 19]).max() < 1e-5
    assert np.array_equal(np.asarray(t3[0]), np.asarray(t2[0]))   # no real token
    assert np.array_equal(np.asarray(t3[1]), np.asarray(x[1, 17:20]))


def test_state_leaves_have_the_stated_layout():
    leaves = ls.init_state(6, 64, 64, 128, 128, 24576, 4)
    shapes = jax.tree.map(lambda a: (a.shape, str(a.dtype)), leaves)
    assert shapes == {
        "state": ((6, 64, 64, 128, 128), "float32"),
        "conv": ((6, 64, 3, 24576), "bfloat16"),
        "state_pos": ((64,), "int32")}
    assert ls.CHUNK == 64
    with pytest.raises(ValueError, match="power of two"):
        ls.unit_lower_inverse(jnp.zeros((3, 3)))
