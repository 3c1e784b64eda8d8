"""ops/ssm_state.py: the chunked form of the state-space recurrence against
the token-by-token step, over chunk boundaries, with padding tokens and an
idle row; and the carried convolution with its bias (ops/linear_state.py's
``short_conv``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import linear_state, ssm_state

B, H, P, N, G = 3, 4, 8, 16, 2


def draws(s: int, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (B, s, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, H)) - 2.0)
    a_head = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (B, s, G, N), jnp.float32)
    c = jax.random.normal(ks[4], (B, s, G, N), jnp.float32)
    d = jax.random.normal(ks[5], (H,), jnp.float32)
    state = jax.random.normal(ks[6], (B, H, P, N), jnp.float32)
    return x, dt, a_head, b, c, d, state


def by_token(x, dt, a_head, b, c, d, state):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm_state.ssd_step(x[:, t], dt[:, t], a_head, b[:, t],
                                      c[:, t], d, state)
        ys.append(y)
    return jnp.stack(ys, axis=1), state


def test_one_step_is_the_recurrence_written_out():
    x, dt, a_head, b, c, d, state = draws(1)
    y, new = ssm_state.ssd_step(x[:, 0], dt[:, 0], a_head, b[:, 0], c[:, 0],
                                d, state)
    x, dt, b, c, a_head, d, state = (np.asarray(t, np.float64) for t in (
        x[:, 0], dt[:, 0], b[:, 0], c[:, 0], a_head, d, state))
    for row in range(B):
        for h in range(H):
            g = h // (H // G)
            want = (np.exp(dt[row, h] * a_head[h]) * state[row, h]
                    + np.outer(dt[row, h] * x[row, h], b[row, g]))
            assert np.abs(np.asarray(new[row, h]) - want).max() < 1e-5
            assert np.abs(np.asarray(y[row, h]) - (
                want @ c[row, g] + d[h] * x[row, h])).max() < 1e-4


@pytest.mark.parametrize("s,chunk", [(16, 16), (48, 16), (40, 16), (5, 16)],
                         ids=["one-chunk", "three-chunks", "ragged", "short"])
def test_the_chunked_form_is_the_token_recurrence(s, chunk):
    """A state carried in, over chunk boundaries, and an S that is not whole
    chunks (padded with identity steps inside ``ssd_scan``)."""
    args = draws(s, seed=s)
    want_y, want_s = by_token(*args)
    got_y, got_s = ssm_state.ssd_scan(*args, chunk)
    assert got_y.shape == want_y.shape
    assert np.abs(np.asarray(got_y - want_y)).max() < 2e-4
    assert np.abs(np.asarray(got_s - want_s)).max() < 2e-4


def test_padding_tokens_are_identity_steps_and_an_idle_row_is_untouched():
    """Real tokens first: row 0 has 20 of 32, row 1 all 32, row 2 none.  A
    step of 0 leaves the state as the last real token left it, and a row of
    identity steps hands its state back bit for bit."""
    x, dt, a_head, b, c, d, state = draws(32, seed=3)
    n_real = np.array([20, 32, 0])
    valid = jnp.asarray(np.arange(32)[None, :] < n_real[:, None])
    dt = jnp.where(valid[..., None], dt, 0.0)
    got_y, got_s = ssm_state.ssd_scan(x, dt, a_head, b, c, d, state, 16)
    short_y, short_s = ssm_state.ssd_scan(
        x[:1, :20], dt[:1, :20], a_head, b[:1, :20], c[:1, :20], d, state[:1],
        16)
    assert np.abs(np.asarray(got_y[0, :20] - short_y[0])).max() < 2e-4
    assert np.abs(np.asarray(got_s[0] - short_s[0])).max() < 2e-4
    assert np.array_equal(np.asarray(got_s[2]), np.asarray(state[2]))
    _, stepped = ssm_state.ssd_step(x[:, 0], jnp.zeros((B, H)), a_head,
                                    b[:, 0], c[:, 0], d, state)
    assert np.array_equal(np.asarray(stepped), np.asarray(state))


def test_a_strong_decay_neither_overflows_nor_leaks_across_the_chunk():
    """Every exponent of the chunked form is <= 0: a head that forgets within
    a token (Δ·A = -60) reads 0 from the past, not inf · 0."""
    x, dt, a_head, b, c, d, state = draws(32, seed=5)
    a_head = a_head.at[0].set(-60.0)
    dt = dt.at[:, :, 0].set(1.0)
    want_y, want_s = by_token(x, dt, a_head, b, c, d, state)
    got_y, got_s = ssm_state.ssd_scan(x, dt, a_head, b, c, d, state, 16)
    assert np.isfinite(np.asarray(got_y)).all()
    assert np.abs(np.asarray(got_y - want_y)).max() < 2e-4
    assert np.abs(np.asarray(got_s - want_s)).max() < 2e-4


def test_the_carried_convolution_adds_its_bias_and_keeps_its_tail():
    """x‖B‖C of 40 columns through the convolution in two dispatches (7 then
    5 tokens, the second padded to 8) against one of 12: the outputs, the
    bias on every one, and the tail that comes back."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (2, 12, 40), jnp.float32)
    w = jax.random.normal(ks[1], (40, 4), jnp.float32)
    bias = jax.random.normal(ks[2], (40,), jnp.float32)
    zeros = jnp.zeros((2, 3, 40), jnp.float32)
    whole, tail = linear_state.short_conv(x, w, zeros, jnp.array([12, 12]), bias)
    plain, _ = linear_state.short_conv(x, w, zeros, jnp.array([12, 12]))
    assert np.abs(np.asarray(whole - plain - bias)).max() < 1e-6
    first, t1 = linear_state.short_conv(x[:, :7], w, zeros, jnp.array([7, 7]),
                                        bias)
    padded = jnp.concatenate([x[:, 7:], jnp.zeros((2, 3, 40))], axis=1)
    second, t2 = linear_state.short_conv(padded, w, t1, jnp.array([5, 5]), bias)
    got = jnp.concatenate([first, second[:, :5]], axis=1)
    assert np.abs(np.asarray(got - whole)).max() < 1e-5
    assert np.array_equal(np.asarray(t2), np.asarray(tail))
    assert np.array_equal(np.asarray(tail), np.asarray(x[:, 9:]))
    # a row with no real token hands its tail back
    _, kept = linear_state.short_conv(padded, w, t1, jnp.array([0, 5]), bias)
    assert np.array_equal(np.asarray(kept[0]), np.asarray(t1[0]))
