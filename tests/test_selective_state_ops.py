"""ops/selective_state.py and its two kernels (ops/pallas/selective_state.py,
interpreted): the scan is the step token by token, Δ = 0 is the identity, a
fresh slot starts from zeros and an idle one keeps its state bit for bit, and
the gates that choose a kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import selective_state as ss
from dynamo_tpu.ops.pallas import registry as reg
from dynamo_tpu.ops.pallas import selective_state as kernel

N, ROWS = 16, 8


def vectors(rng, lead):
    return tuple(jnp.asarray(v, jnp.float32)
                 for v in reg._selective_vectors(rng, lead, N, ROWS))


@pytest.mark.parametrize("channels", ["rows-of-lanes", "flat"])
def test_the_scan_is_the_step_token_by_token(channels):
    """Whatever the channel axes: [R, 128] as the state lies, or flat."""
    x, dt, a, b, c = vectors(np.random.default_rng(1), (3, 20))
    state = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, N, ROWS, 128)), jnp.float32)
    if channels == "flat":
        x, dt = (t.reshape(3, 20, -1) for t in (x, dt))
        a, state = a.reshape(N, -1), state.reshape(3, N, -1)
    y, last = ss.selective_scan(x, dt, a, b, c, state)
    st, ys = state, []
    for t in range(20):
        yt, st = ss.selective_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], st)
        ys.append(yt)
    assert np.abs(jnp.stack(ys, 1) - y).max() < 1e-5
    assert np.abs(st - last).max() < 1e-6
    # against the recurrence written out for one channel and index
    h, want = np.asarray(state)[0, 3].reshape(-1)[5], None
    for t in range(20):
        d, xv = (float(np.asarray(v)[0, t].reshape(-1)[5]) for v in (dt, x))
        h = np.exp(d * float(np.asarray(a)[3].reshape(-1)[5])) * h \
            + d * xv * float(b[0, t, 3])
    assert abs(h - float(np.asarray(last)[0, 3].reshape(-1)[5])) < 1e-5


def test_a_step_of_zero_is_the_identity():
    """Padding: Δ = 0 leaves the state as it was, bit for bit, in the step,
    in the scan and in the scan's kernel; ``y`` is then h C alone."""
    x, dt, a, b, c = vectors(np.random.default_rng(3), (2, 16))
    state = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, N, ROWS, 128)), jnp.float32)
    zero = jnp.zeros_like(dt)
    y, new = ss.selective_step(x[:, 0], zero[:, 0], a, b[:, 0], c[:, 0], state)
    assert np.array_equal(new, state)
    assert np.abs(y - (state * c[:, 0][:, :, None, None]).sum(1)).max() < 1e-5
    _, new = ss.selective_scan(x, zero, a, b, c, state)
    assert np.array_equal(new, state)
    leaf = jnp.stack([state, state])
    _, got = kernel.state_scan.__wrapped__(
        leaf, jnp.int32(1), jnp.asarray([1, 0]), x, zero, a, b, c,
        jnp.asarray([False, False]), interpret=True)
    assert np.array_equal(got, jnp.stack([state, state]))


@pytest.mark.parametrize("case", ["selective-step", "selective-scan"])
def test_a_kernel_is_its_xla_form(case):
    """The registry's audit case of each kernel, clean and poisoned (the
    fresh slot full of NaN beforehand, the decode's idle slot too): the live
    part equals the oracle, the idle slot's ``y`` is exact zeros."""
    spec = next(c for c in reg.audit_cases() if c["name"] == case)
    inp = spec["build"]()
    ref, live, zero = spec["oracle"](inp)
    for poisoned in (False, True):
        got = np.asarray(spec["run"](inp, poisoned))
        assert np.abs(got[live] - ref[live]).max() < spec["atol"]
        assert not got[zero].any()
        assert np.isfinite(got[live]).all()


def test_a_chunk_longer_than_a_call_s_scalars_goes_in_pieces(monkeypatch):
    """B and C travel as scalars, ``SELECTIVE_SCAN_SCALARS`` of each a call:
    a chunk over that is cut along its tokens, the state carried from piece
    to piece and ``fresh`` honoured by the first alone."""
    x, dt, a, b, c = vectors(np.random.default_rng(5), (1, 40))
    leaf = jnp.asarray(np.random.default_rng(6).normal(
        size=(1, 2, N, ROWS, 128)), jnp.float32)
    args = (jnp.int32(0), jnp.asarray([1]), x, dt, a, b, c,
            jnp.asarray([True]))
    whole_y, whole = kernel.state_scan.__wrapped__(
        jnp.array(leaf), *args, interpret=True)
    monkeypatch.setattr(kernel, "SELECTIVE_SCAN_SCALARS", 16 * N)
    y, got = kernel.state_scan.__wrapped__(
        jnp.array(leaf), *args, interpret=True)
    assert np.abs(y - whole_y).max() < 1e-6
    assert np.abs(got - whole).max() < 1e-6
    assert np.array_equal(got[0, 0], leaf[0, 0])        # the other slot


def test_the_gates_choose_a_kernel_on_the_tpu_alone(monkeypatch):
    assert ss.step_impl(16, 40, 128, jnp.float32) == ("xla", "backend is cpu")
    assert ss.scan_impl(16, 40, 128, jnp.float32) == ("xla", "backend is cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ss.step_impl(16, 40, 128, jnp.float32) == ("pallas", "tpu")
    assert ss.scan_impl(16, 40, 128, jnp.float32) == ("pallas", "tpu")
    # a bf16 state (the check's negative control), channels that do not fill
    # rows of lanes, rows that do not fill registers: the XLA forms
    for n, rows, lanes, dtype in ((16, 40, 128, jnp.bfloat16),
                                  (16, 1, 96, jnp.float32),
                                  (16, 4, 128, jnp.float32)):
        for impl in (ss.step_impl, ss.scan_impl):
            how, why = impl(n, rows, lanes, dtype)
            assert how == "xla" and "do not tile" in why
    monkeypatch.setenv("DYNAMO_DISABLE_PALLAS", "1")
    assert ss.step_impl(16, 40, 128, jnp.float32)[0] == "xla"
    # the kernels' work as the registry prices it: a row's state once in and
    # once out, 16 x 5,120 exponentials a token
    cost = reg.selective_step_cost(64, 16, 5120)
    assert cost["hbm_bytes"] == 64 * (2 * 327_680 + (3 * 5120 + 32) * 4)
    assert cost["transcendentals"] == 64 * 81_920
    cost = reg.selective_scan_cost(1, 512, 16, 5120)
    assert cost["transcendentals"] == 512 * 81_920
    assert cost["hbm_bytes"] == 2 * 327_680 + 512 * (3 * 5120 + 32) * 4
