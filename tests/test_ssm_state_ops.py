"""ops/ssm_state.py: the chunked form of the state-space recurrence against
the token-by-token step, over chunk boundaries, with padding tokens and an
idle row; the carried convolution with its bias (ops/linear_state.py's
``short_conv``); and the decode step's kernel (ops/pallas/ssm_state.py),
interpreted, against ``ssd_step``, with the rule that chooses between them."""

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


# ----- the decode step's kernel (ops/pallas/ssm_state.py), interpreted ------
# two steps of sixteen heads a slot (eight rows of x at two heads a 128-lane
# row): a dead slot's steps name another's block
KL, KB, KH, KP, KN, KS = 3, 4, 32, 64, 128, 16
_ALL, _NONE = (True,) * KB, (False,) * KB


@pytest.mark.parametrize("layer,alive,fresh,poison,groups,strength", [
    (1, _ALL, _NONE, (), 1, 1.0),
    # three dead slots full of NaN: bit for bit afterwards, and the live
    # row's y and state as if they held numbers
    (1, (True, False, False, False), _NONE, (1, 2, 3), 1, 1.0),
    # ... in front of the first live slot, and with no slot alive at all
    (1, (False, False, True, True), _NONE, (0, 1), 1, 1.0),
    (1, _NONE, _NONE, (0, 2), 1, 1.0),
    # a fresh row over a slot full of NaN reads as from zeros
    (1, _ALL, (False, True, False, False), (1,), 1, 1.0),
    # a head that forgets within the token, and one that forgets nothing
    (1, _ALL, _NONE, (), 1, 600.0),
    (1, _ALL, _NONE, (), 1, 0.0),
    # B and C a group of sixteen heads: a step's heads read their own group's
    (1, (True, True, False, True), _NONE, (2,), 2, 1.0),
    (0, (True, True, False, True), (True, False, False, False), (2,), 1, 1.0),
    (KL - 1, (True, True, False, True), (False, False, False, True), (2,), 2,
     1.0),
], ids=["all-alive", "quarter-alive-poisoned", "dead-in-front", "none-alive",
        "fresh-over-poison", "strong-decay", "no-decay", "two-groups",
        "layer-0", "layer-last-two-groups"])
def test_state_update_kernel_is_the_token_step(layer, alive, fresh, poison,
                                               groups, strength):
    from dynamo_tpu.ops.pallas.ssm_state import state_update

    ks = jax.random.split(jax.random.PRNGKey(layer), 7)
    x = jax.random.normal(ks[0], (KB, KH, KP), jnp.float32)
    dt = strength * jax.nn.softplus(jax.random.normal(ks[1], (KB, KH)) - 2.0)
    a_head = -jnp.exp(jax.random.uniform(ks[2], (KH,), minval=0.0, maxval=2.5))
    b = jax.random.normal(ks[3], (KB, groups, KN), jnp.float32)
    c = jax.random.normal(ks[4], (KB, groups, KN), jnp.float32)
    d = jax.random.normal(ks[5], (KH,), jnp.float32)
    clean = jax.random.normal(ks[6], (KL, KB, KH, KP, KN), jnp.float32)
    alive, fresh = np.asarray(alive), np.asarray(fresh)
    held = np.array(clean)
    held[layer, list(poison)] = np.nan
    # the oracle never sees the poison: a fresh row starts from zeros
    start = jnp.where(fresh[:, None, None, None], 0, clean[layer])
    want_y, want_s = ssm_state.ssd_step(x, dt, a_head, b, c, d, start)
    got_y, got = state_update(jnp.asarray(held), jnp.int32(layer), x, dt,
                              a_head, b, c, d, jnp.asarray(fresh),
                              jnp.asarray(alive), heads_per_step=KS,
                              interpret=True)
    got_y, got = np.asarray(got_y), np.asarray(got)
    others = [i for i in range(KL) if i != layer]
    assert np.array_equal(got[others], held[others], equal_nan=True)
    assert np.array_equal(got[layer][~alive], held[layer][~alive],
                          equal_nan=True)
    assert np.array_equal(got_y[~alive], np.zeros_like(got_y[~alive]))
    if alive.any():
        assert not np.isnan(got[layer][alive]).any()
        scale = max(1.0, float(np.abs(want_s).max()))
        assert np.abs(got[layer] - want_s)[alive].max() < 2e-6 * scale
        # y sums 128 products of the state
        assert np.abs(got_y - want_y)[alive].max() < 2e-5 * scale


def test_the_kernel_s_step_is_the_registry_s_for_the_geometry():
    """Without ``heads_per_step`` a grid step takes what the registry gives
    the geometry — here all 32 heads of one group at once, one step a slot —
    and a step that would straddle two groups of B and C is refused."""
    from dynamo_tpu.ops.pallas import registry
    from dynamo_tpu.ops.pallas.ssm_state import state_update

    args = registry.probe_ssm_state_inputs(2, 4, 32, 64, 128, 1)
    want = registry.ssm_state_reference(*args)
    y, new = state_update(*args, interpret=True)
    got = registry.linear_state_rows(y, new[args[1]])
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    two = registry.probe_ssm_state_inputs(2, 4, 32, 64, 128, 2)
    with pytest.raises(ValueError, match="32 heads of 2 groups in steps of 32"):
        state_update(*two, heads_per_step=32, interpret=True)


@pytest.mark.parametrize("geometry,tiles", [
    ((128, 64, 128, 1, jnp.float32), 32),   # granite-4.0-h-small: 1 MiB a step
    ((128, 64, 128, 8, jnp.float32), 16),   # its heads in eight groups of B, C
    ((48, 64, 128, 1, jnp.float32), 16),
    ((16, 128, 128, 1, jnp.float32), 16),   # a head a row of x: steps of 8
    ((128, 64, 128, 1, jnp.bfloat16), None),    # the check's control
    ((128, 64, 128, 16, jnp.float32), None),    # eight heads a group: half
                                                # a tile of x rows
    ((128, 64, 128, 3, jnp.float32), None),     # groups that do not divide
    ((4, 32, 16, 1, jnp.float32), None),        # the toy: N under the lanes
    ((128, 48, 128, 1, jnp.float32), None),     # heads that split a row of x
    ((16, 256, 128, 1, jnp.float32), None),     # P wider than a row of x
], ids=["granite", "eight-groups", "48-heads", "p-128", "bf16-state",
        "8-heads-a-group", "ragged-groups", "toy", "p-48", "p-256"])
def test_state_update_rule_is_the_oracle_off_the_tpu(monkeypatch, geometry,
                                                     tiles):
    """On the CPU, under DYNAMO_DISABLE_PALLAS, and for a state the kernel
    does not tile, the step is ``ssd_step``; on the TPU otherwise the kernel,
    at the registry's heads a step."""
    from dynamo_tpu.ops.pallas import registry
    from dynamo_tpu.ops.pallas.ssm_state import state_update_supported

    heads, p, n, groups, dtype = geometry
    assert ssm_state.step_impl(*geometry) == ("xla", "backend is cpu")
    assert state_update_supported(*geometry) == (tiles is not None)
    if tiles:
        assert registry.ssm_state_heads_per_step(heads, groups, p, n) == tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl, why = ssm_state.step_impl(*geometry)
    assert (impl, why == "tpu") == (("pallas", True) if tiles
                                    else ("xla", False))
    if not tiles:
        assert why.endswith("do not tile") and str(heads) in why
    monkeypatch.setenv("DYNAMO_DISABLE_PALLAS", "1")
    assert ssm_state.step_impl(*geometry) == (
        "xla", "DYNAMO_DISABLE_PALLAS is set")
