"""The two operations ZAYA's attention adds beside what was there: a causal
convolution over time that mixes the channels of a head and carries its tail
(ops/linear_state.py ``grouped_conv``), and a rotary width smaller than the
head (models/llama.py ``apply_rope(rotary_dim=)``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import apply_rope
from dynamo_tpu.ops.linear_state import grouped_conv, short_conv


def naive_grouped(x, w, bias):
    """One sequence [T, G·Di], zeros before position 0, a Python loop."""
    kk, g, di, do = w.shape
    t = x.shape[0]
    xg = np.concatenate([np.zeros((kk - 1, g, di)), x.reshape(t, g, di)])
    y = np.zeros((t, g, do))
    for pos in range(t):
        for i in range(kk):
            for grp in range(g):
                y[pos, grp] += xg[pos + i, grp] @ w[i, grp]
    return y.reshape(t, g * do) + bias


@pytest.mark.parametrize("taps", [2, 3])
def test_grouped_conv_in_pieces_with_its_tail_is_the_whole_sequence(taps):
    """24 tokens whole, and as 10 + 9 (padded to 12) + five single tokens
    with the tail carried: equal to a loop over positions, groups and taps.
    A row with no real token gets its tail back bit for bit."""
    rng = np.random.default_rng(taps)
    g, di, do, t = 3, 4, 4, 24
    x = rng.normal(size=(2, t, g * di)).astype(np.float32)
    w = rng.normal(size=(taps, g, di, do)).astype(np.float32)
    bias = rng.normal(size=(g * do,)).astype(np.float32)
    want = np.stack([naive_grouped(x[b], w, bias) for b in range(2)])
    zero = jnp.zeros((2, taps - 1, g * di), jnp.float32)
    whole, _ = grouped_conv(jnp.asarray(x), jnp.asarray(w), zero,
                            jnp.array([t, t]), jnp.asarray(bias))
    assert np.abs(np.asarray(whole) - want).max() < 1e-5
    got, tail = [], zero
    y, tail = grouped_conv(jnp.asarray(x[:, :10]), w, tail, jnp.array([10, 10]), bias)
    got.append(y)
    padded = np.concatenate([x[:, 10:19], np.full((2, 3, g * di), 7.0, np.float32)], axis=1)
    y, tail = grouped_conv(jnp.asarray(padded), w, tail, jnp.array([9, 9]), bias)
    got.append(y[:, :9])
    for pos in range(19, 24):
        # row 1 sits idle for one step in the middle and then goes on
        y, new = grouped_conv(jnp.asarray(x[:, pos:pos + 1]), w, tail,
                              jnp.array([1, 1]), bias)
        idle, kept = grouped_conv(jnp.asarray(x[:, pos:pos + 1]), w, tail,
                                  jnp.array([1, 0]), bias)
        assert np.array_equal(np.asarray(kept[1]), np.asarray(tail[1]))
        assert np.array_equal(np.asarray(kept[0]), np.asarray(new[0]))
        got.append(y)
        tail = new
    assert np.abs(np.concatenate(got, axis=1) - want).max() < 1e-5


def test_grouped_conv_of_diagonal_matrices_is_the_depth_wise_one():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)).astype(np.float32))
    wd = rng.normal(size=(6, 2)).astype(np.float32)             # [D, K]
    tail = jnp.asarray(rng.normal(size=(2, 1, 6)).astype(np.float32))
    n = jnp.array([9, 5])
    w = np.zeros((2, 2, 3, 3), np.float32)
    for i in range(2):
        for grp in range(2):
            w[i, grp] = np.diag(wd[grp * 3:(grp + 1) * 3, i])
    y, t1 = grouped_conv(x, jnp.asarray(w), tail, n)
    y2, t2 = short_conv(x, jnp.asarray(wd), tail, n)
    assert np.abs(np.asarray(y - y2)).max() < 1e-6
    assert np.array_equal(np.asarray(t1), np.asarray(t2))


def test_partial_rotary_leaves_the_rest_of_the_head_unrotated():
    """``rotary_dim`` 64 of 128: dimensions 64-127 pass bit for bit, 0-63
    are the rotate-half of a 64-wide head (halves 32 apart, frequencies over
    64), and the whole width — or no ``rotary_dim`` — is what it was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 128), jnp.float32)
    pos = jnp.array([[0, 1, 2, 3, 4], [100, 101, 102, 5000, 131071]])
    got = apply_rope(x, pos, 5e6, rotary_dim=64)
    assert np.array_equal(np.asarray(got[..., 64:]), np.asarray(x[..., 64:]))
    assert np.array_equal(np.asarray(got[..., :64]),
                          np.asarray(apply_rope(x[..., :64], pos, 5e6)))
    assert np.array_equal(np.asarray(got[0, 0]), np.asarray(x[0, 0]))   # pos 0
    assert np.abs(np.asarray(got[1, 4, :, :64] - x[1, 4, :, :64])).max() > 0.1
    inv = 1.0 / 5e6 ** (np.arange(32) * 2.0 / 64)
    ang = 101 * inv
    x1, x2 = np.asarray(x[1, 1, 0, :32]), np.asarray(x[1, 1, 0, 32:64])
    assert np.abs(np.asarray(got[1, 1, 0, :32])
                  - (x1 * np.cos(ang) - x2 * np.sin(ang))).max() < 1e-4
    whole = apply_rope(x, pos, 5e6)
    assert np.array_equal(np.asarray(apply_rope(x, pos, 5e6, rotary_dim=128)),
                          np.asarray(whole))
    assert np.abs(np.asarray(whole[..., 64:] - x[..., 64:])).max() > 0.1
