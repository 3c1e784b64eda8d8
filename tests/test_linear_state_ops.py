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


# ----- the decode step's kernel (ops/pallas/linear_state.py), interpreted ----
# two groups of eight heads a slot: a dead slot's steps name another's block
KL, KB, KH, KD, KG = 3, 4, 16, 128, 8
_ALL, _NONE = (True,) * KB, (False,) * KB


@pytest.mark.parametrize("layer,alive,fresh,poison,decay,beta_shift,like_keys", [
    (1, _ALL, _NONE, (), 0.5, 0.0, False),
    # three dead slots full of NaN: bit for bit afterwards, and the live
    # row's o and state as if they held numbers
    (1, (True, False, False, False), _NONE, (1, 2, 3), 0.5, 0.0, False),
    # ... in front of the first live slot, and with no slot alive at all
    (1, (False, False, True, True), _NONE, (0, 1), 0.5, 0.0, False),
    (1, _NONE, _NONE, (0, 2), 0.5, 0.0, False),
    # a fresh row over a slot full of NaN reads as from zeros
    (1, _ALL, (False, True, False, False), (1,), 0.5, 0.0, False),
    (1, _ALL, _NONE, (), 50.0, 0.0, False),
    (1, _ALL, _NONE, (), 1e-4, 0.0, False),
    (1, _ALL, _NONE, (), 0.05, 5.0, True),
    (0, (True, True, False, True), (True, False, False, False), (2,), 0.5,
     0.0, False),
    (KL - 1, (True, True, False, True), (False, False, False, True), (2,),
     0.5, 0.0, False),
], ids=["all-alive", "quarter-alive-poisoned", "dead-in-front", "none-alive",
        "fresh-over-poison", "strong-decay", "no-decay", "beta-2-like-keys",
        "layer-0", "layer-last"])
def test_state_update_kernel_is_the_token_step(layer, alive, fresh, poison,
                                               decay, beta_shift, like_keys):
    from dynamo_tpu.ops.pallas.linear_state import state_update

    ks = jax.random.split(jax.random.PRNGKey(layer), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (KB, KH, KD))) * KD ** -0.5
    k = jax.random.normal(ks[1], (KB, KH, KD))
    if like_keys:
        k = k[:1] + 0.05 * k
    k = unit(k)
    v = jax.random.normal(ks[2], (KB, KH, KD))
    g = -decay * jax.random.uniform(ks[3], (KB, KH, KD))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (KB, KH)) + beta_shift)
    clean = jax.random.normal(ks[5], (KL, KB, KH, KD, KD))
    alive, fresh = np.asarray(alive), np.asarray(fresh)
    held = np.array(clean)
    held[layer, list(poison)] = np.nan
    # the oracle never sees the poison: a fresh row starts from zeros
    start = jnp.where(fresh[:, None, None, None], 0, clean[layer])
    want_o, want_s = ls.delta_rule_step(q, k, v, g, beta, start)
    got_o, got = state_update(jnp.asarray(held), jnp.int32(layer), q, k, v,
                              g, beta, jnp.asarray(fresh), jnp.asarray(alive),
                              heads_per_step=KG, interpret=True)
    got_o, got = np.asarray(got_o), np.asarray(got)
    others = [i for i in range(KL) if i != layer]
    assert np.array_equal(got[others], held[others], equal_nan=True)
    assert np.array_equal(got[layer][~alive], held[layer][~alive],
                          equal_nan=True)
    assert np.array_equal(got_o[~alive], np.zeros_like(got_o[~alive]))
    if alive.any():
        assert not np.isnan(got[layer][alive]).any()
        scale = max(1.0, float(np.abs(want_s).max()))
        assert np.abs(got[layer] - want_s)[alive].max() < 2e-6 * scale
        assert np.abs(got_o - want_o)[alive].max() < 2e-6 * scale


def test_a_dead_slot_names_the_block_that_is_resident():
    """What a grid step's matrices are by ``_resident``: a live slot's own
    (group -1); a dead slot's the last group of the live slot before it, so
    that the pipeline sees the index it has and moves nothing; the dead
    slots in front of the first live one that one's first group; slot 0's
    first with nobody alive (the kernel then copies it through)."""
    from dynamo_tpu.ops.pallas.linear_state import _resident

    def named(*alive):
        row, group = _resident(jnp.asarray(alive), 4)
        return list(zip(np.asarray(row).tolist(), np.asarray(group).tolist()))

    assert named(True, True, True) == [(0, -1), (1, -1), (2, -1)]
    assert named(True, False, False, True, False) == [
        (0, -1), (0, 3), (0, 3), (3, -1), (3, 3)]
    assert named(False, False, True, False) == [(2, 0), (2, 0), (2, -1), (2, 3)]
    assert named(False, False) == [(0, 0), (0, 0)]


def test_state_update_rule_is_the_oracle_off_the_tpu(monkeypatch):
    """On the CPU, and for a state the kernel does not tile, the step is
    ``delta_rule_step``; on the TPU at the published widths the kernel."""
    assert ls.step_impl(64, 128, 128, jnp.float32) == ("xla", "backend is cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ls.step_impl(64, 128, 128, jnp.float32) == ("pallas", "tpu")
    assert ls.step_impl(64, 128, 128, jnp.bfloat16)[0] == "xla"
    assert ls.step_impl(4, 16, 16, jnp.float32)[0] == "xla"
    monkeypatch.setenv("DYNAMO_DISABLE_PALLAS", "1")
    assert ls.step_impl(64, 128, 128, jnp.float32)[0] == "xla"
