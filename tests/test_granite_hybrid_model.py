"""models/hybrid_linear.py with the state-space recurrence
(``granitemoehybrid``) against the benchmark's plain reference
(cellbench/reference/granite_hybrid.py) by direct calls of ``forward``:
prefill in chunks then decode through the cache, what each of Granite's four
multipliers and the gate inside the norm are worth, the two EP2 shares of an
expert layer, and what ``from_hf_config`` refuses."""

import importlib.util
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from granite_hybrid_tiny import ROUNDING, TINY, build, ref, want
from hybrid_linear_tiny import ROOT, tokens_of
from test_hybrid_linear_model import chunk, decode, fresh_cache


def served(model, params, toks, other):
    """80 tokens: 75 in chunks of 32, 32 and 11 (the last padded to 16; a
    chunk is two SSD pieces of 16) in slot 2 behind a 20-token sequence in
    slot 0, then five decode steps beside it: every position's
    log-probabilities, and the cache."""
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, other, 0, 20, 0, 20)
    got = []
    for a, b, pad in ((0, 32, None), (32, 64, None), (64, 75, 16)):
        lp, cache = chunk(model, params, cache, toks, a, b, 2, 1, pad)
        got.append(lp)
    for n in range(75, 80):
        lp, cache = decode(model, params, cache, {
            2: (n, 1, toks[n]), 0: (n - 55, 20, other[n - 55])})
        got.append(lp[2:3])
    return np.concatenate(got), cache


def test_prefill_in_chunks_then_decode_is_the_reference():
    """float32 on both sides: what is left between the program (chunked
    recurrence, carried convolution, paged attention) and the reference (one
    token at a time, one full forward) is the order of the sums."""
    model, params = build()
    assert [(r.kind, r.count) for r in model.runs] == [
        ("linear", 2), ("gqa", 1), ("linear", 3)]
    assert "lm_head" not in params and "router_bias" not in params["groups"]["gqa"]
    assert "w_gate_attn" not in params["groups"]["gqa"]
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, cache = served(model, params, toks, other)
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING
    counts = np.asarray(cache["moe_counts"])
    assert counts[0, 0, 4] == 5 * (20 + 75 + 2 * 5)        # tokens x layers
    assert counts[0, 0, 5] == 2 and counts[0, 0, 6] == 0   # resets, mismatches
    assert list(np.asarray(cache["state_pos"])) == [25, 0, 80, 0]
    assert cache["state"].shape == (5, 4, 4, 32, 16)
    assert cache["conv"].shape == (5, 4, 3, 128 + 2 * 16)
    assert cache["kv"].shape[0] == 1
    assert model.state_update_impl()[0] == "xla"


def test_a_decode_through_the_state_kernel_is_the_decode_through_xla(
        monkeypatch):
    """The decode branch of ``_ssd`` that the TPU takes (the state updated
    where it lies by ops/pallas/ssm_state.py, here interpreted), against the
    slice / ``ssd_step`` / set form on the same cache: the live rows'
    log-probabilities, state and ``conv``, the idle slots bit for bit,
    ``state_pos`` and the counts equal.  Sixteen heads of 64 x 128: what the
    kernel tiles."""
    import functools

    from dynamo_tpu.models import hybrid_linear
    from dynamo_tpu.ops.pallas.ssm_state import state_update

    model, params = build(dict(TINY, mamba_n_heads=16, mamba_d_head=64,
                               mamba_d_state=128, mamba_expand=16))
    assert model.state_update_impl() == ("xla", "backend is cpu")
    toks = tokens_of(30, 4)
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, toks, 0, 24, 2, 1)
    # slot 1 holds what a finished request left; slot 3 starts at position 0
    cache["state"] = cache["state"].at[:, 1].set(7.0)
    rows = {2: (24, 1, toks[24]), 3: (0, 30, toks[0])}
    want_lp, want_cache = decode(model, params, cache, rows)
    monkeypatch.setattr(model, "state_update_impl", lambda: ("pallas", "test"))
    monkeypatch.setattr(hybrid_linear, "ssm_state_update",
                        functools.partial(state_update, interpret=True))
    got_lp, got_cache = decode(model, params, jax.tree.map(jnp.array, cache),
                               rows)
    assert np.abs(got_lp[[2, 3]] - want_lp[[2, 3]]).max() < 1e-4
    got_s, want_s = (np.asarray(c["state"]) for c in (got_cache, want_cache))
    assert np.abs(got_s[:, [2, 3]] - want_s[:, [2, 3]]).max() < 1e-5
    assert np.array_equal(got_s[:, [0, 1]], np.asarray(cache["state"])[:, [0, 1]])
    # a later layer's inputs carry the earlier layers' rounding
    assert np.abs(np.asarray(got_cache["conv"])
                  - np.asarray(want_cache["conv"])).max() < 1e-4
    assert np.array_equal(np.asarray(got_cache["state_pos"]),
                          np.asarray(want_cache["state_pos"]))
    # all but the experts touched (column 3), which count every row's picks:
    # an idle row's y is zero here and the padding token's there
    got_n, want_n = (np.delete(np.asarray(c["moe_counts"]), 3, axis=-1)
                     for c in (got_cache, want_cache))
    assert np.array_equal(got_n, want_n)


@pytest.mark.parametrize("left_out", [
    {"embedding_multiplier": 1}, {"residual_multiplier": 1},
    {"logits_scaling": 1}, {"attention_multiplier": 0.25}],
    ids=lambda d: next(iter(d)))
def test_a_multiplier_left_out_is_not_the_reference(left_out):
    """The same weights (no draw depends on a multiplier) served with one of
    the four at its neutral value — the softmax scale at d^-1/2 — against
    the reference at the published one: far outside the rounding."""
    model, params = build(dict(TINY, **left_out))
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, _ = served(model, params, toks, other)
    assert np.abs(got - want(params, toks, np.arange(80))).max() > 10 * ROUNDING


def test_the_gate_outside_the_norm_is_not_the_reference():
    """RMSNorm(y) ⊙ SiLU(z) in place of RMSNorm(y ⊙ SiLU(z)): the reference
    with that one line turned round is another model, which the comparison
    above would not pass."""
    path = ROOT / "cellbench/reference/granite_hybrid.py"
    right = 'rms_norm(y * jax.nn.silu(z), lp["out_norm"], cfg["rms_norm_eps"])'
    wrong = 'rms_norm(y, lp["out_norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)'
    source = path.read_text()
    assert source.count(right) == 1
    spec = importlib.util.spec_from_loader("_granite_gate_outside", None)
    turned = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = turned
    exec(compile(source.replace(right, wrong), str(path), "exec"),
         turned.__dict__)
    model, params = build()
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, _ = served(model, params, toks, other)
    other_model = np.asarray(turned.make_forward(TINY)(
        params, jnp.asarray(toks, jnp.int32), jnp.arange(80)))
    assert np.abs(got - other_model).max() > 10 * ROUNDING
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING


def test_the_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Two chips of four experts each: the parts their held experts give,
    the shared MLP counted once, are the layer over all eight — in the
    reference and in the program's own ``_experts`` (whose sum carries
    ``residual_multiplier``)."""
    whole_cfg = dict(TINY, num_local_experts=8, expert_parallel={
        "chips": 1, "router_experts": 8, "first_expert": 0})
    model, params = build(whole_cfg)
    group = params["groups"]["linear"]
    lp = jax.tree.map(lambda a: a[1], group)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    routed_all, shared_all = ref.make_layer(whole_cfg)(lp, x[0])
    # a softmax over the three largest logits: each token's gates sum to 1
    assert np.allclose(np.asarray(ref.gates(x[0], lp, whole_cfg)).sum(-1), 1.0)
    total = np.zeros_like(np.asarray(routed_all))
    picks = 0
    for first in (0, 4):
        cfg = dict(TINY, expert_parallel={
            "chips": 2, "router_experts": 8, "first_expert": first})
        stacks = {k: group[k][:, first:first + 4]
                  for k in ("w_gate", "w_up", "w_down")}
        share = {**lp, **{k: v[1] for k, v in stacks.items()}}
        routed, shared = ref.make_layer(cfg)(share, x[0])
        assert np.abs(shared - shared_all).max() == 0
        total += np.asarray(routed)
        part = HybridLinearModel(
            HybridLinearConfig.from_hf_config(cfg, dtype="float32"))
        y, counted = part._experts({**group, **stacks}, share, 1, x, valid)
        normed = ref.rms_norm(x[0], lp["mlp_norm"], TINY["rms_norm_eps"])
        r2, s2 = ref.make_layer(cfg)(share, normed)
        assert np.abs(np.asarray(y[0] - x[0])
                      - TINY["residual_multiplier"] * np.asarray(r2 + s2)
                      ).max() < 1e-4
        assert int(counted[0]) == 24 * 3
        picks += int(counted[1])
    assert picks == 24 * 3                  # every pick is held by one share
    assert np.abs(total - np.asarray(routed_all)).max() < 1e-4


@pytest.mark.parametrize("change,words", [
    ({"layer_types": ["mamba"] * 5}, "layer_types"),
    ({"layer_types": ["mamba"] * 5 + ["sliding"]}, "layer_types"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"normalization_function": "layernorm"}, "normalization_function"),
])
def test_from_hf_config_refuses_by_name_what_it_does_not_compute(change, words):
    with pytest.raises(NotImplementedError, match=words):
        HybridLinearConfig.from_hf_config({**TINY, **change})
