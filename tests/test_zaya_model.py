"""models/zaya.py against the benchmark's plain reference
(cellbench/reference/zaya_cca.py) by direct calls of ``forward``: prefill in
chunks then decode through the cache (log-probabilities, not tokens), a
prompt split at every chunk position, what each term of the layer is worth,
the skip output, the router's carried state and what ``from_hf_config``
refuses."""

import importlib.util
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.zaya import ZayaConfig, ZayaModel, route
from hybrid_linear_tiny import ROOT, tokens_of
from test_hybrid_linear_model import chunk, decode, fresh_cache
from zaya_tiny import ROUNDING, TINY, build, ref, want


def served(model, params, toks, other, chunks=((0, 32, None), (32, 64, None),
                                               (64, 75, 16))):
    """80 tokens: 75 in ``chunks`` (the last padded) in slot 2 behind a
    20-token sequence in slot 0, then five decode steps beside it: every
    position's log-probabilities, and the cache."""
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, other, 0, 20, 0, 20)
    got = []
    for a, b, pad in chunks:
        lp, cache = chunk(model, params, cache, toks, a, b, 2, 1, pad)
        got.append(lp)
    for n in range(75, 80):
        lp, cache = decode(model, params, cache, {
            2: (n, 1, toks[n]), 0: (n - 55, 20, other[n - 55])})
        got.append(lp[2:3])
    return np.concatenate(got), cache


def test_prefill_in_chunks_then_decode_is_the_reference():
    """float32 on both sides: what is left between the program (carried
    tails, paged attention, sorted experts) and the reference (one full
    forward, every expert on every token) is the order of the sums."""
    model, params = build()
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, cache = served(model, params, toks, other)
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING
    counts = np.asarray(cache["moe_counts"])
    run = 20 + 75 + 2 * 5
    assert cache["moe_counts"].shape == (4, 1, len(model.moe_count_keys))
    col = {k: counts[..., i].sum() for i, k in enumerate(model.moe_count_keys)}
    assert col["moe_router_picks_total"] == 4 * run == col["state_tokens_total"]
    assert (col["moe_held_picks_total"] + col["moe_skip_picks_total"]
            == col["moe_router_picks_total"])
    assert 0 < col["moe_skip_picks_total"] < col["moe_held_picks_total"]
    assert col["moe_expert_layer_calls_total"] == 4 * 9
    assert 0 < col["moe_experts_touched_total"] <= 4 * 4 * 9
    assert col["state_resets_total"] == 2
    assert col["state_position_mismatches_total"] == 0
    assert list(np.asarray(cache["state_pos"])) == [25, 0, 80, 0]
    assert cache["state"].shape == (4, 4, 2 * 160 + 16)
    assert cache["kv"].shape[0] == 4 and cache["kv"].shape[-1] == 2 * 16
    assert model.state_update_impl()[0] == "xla"


@pytest.mark.parametrize("cut", [8, 16, 24, 40, 56, 72])
def test_a_prompt_split_at_any_chunk_position_is_the_unsplit_prompt(cut):
    """75 tokens as [0, cut) and [cut, 75): both convolutions' tails and the
    value shift cross the boundary wherever it lies (a chunk starts on a
    block), and the decode steps behind read the second chunk's."""
    model, params = build()
    toks, other = tokens_of(80, 3), tokens_of(26, 4)
    pad = 1 << (75 - cut - 1).bit_length()
    got, _ = served(model, params, toks, other,
                    chunks=((0, cut, None), (cut, 75, max(pad, 8))))
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING


# one line of the reference turned into what a port that dropped the term
# would compute: the served model must be far from each
LEFT_OUT = {
    "first-convolution-tap": (
        "shifted(c, k0 - 1 - i) * w0[:, i] for i in range(k0)",
        "shifted(c, 0) * w0[:, i] for i in range(k0 - 1, k0)"),
    "grouped-convolution-tap": (
        "shifted(ug, k1 - 1 - i), a[i])\n            for i in range(k1))",
        "shifted(ug, 0), a[i])\n            for i in range(k1 - 1, k1))"),
    "qk-mean": ("q = z[:, :hq] + mq.reshape(t, hq, d)", "q = z[:, :hq]"),
    "key-mean-over-group": ("mk = mq.mean(axis=2)", "mk = mq[:, :, 0]"),
    "temperature": (' * jnp.exp(f32(lp["temp"]))[:, None]', ""),
    "l2-norm": ("q = unit(q) * d ** 0.5", "q = q"),
    "partial-rotary": ('width = int(d * rope["partial_rotary_factor"])',
                       "width = d"),
    "value-shift": ('shifted(x @ f32(lp["wv2"]), 1)', 'x @ f32(lp["wv2"])'),
    "depth-averaging": ('r = r + f32(lp["router_eda"]) * r_prev', "r = r"),
    "router-norm": ('s = rms_norm(r, lp["router_norm"], cfg["rms_norm_eps"])',
                    "s = r"),
    "balancing-bias": ('jnp.argmax(p + f32(lp["router_bias"]), axis=-1)',
                       "jnp.argmax(p, axis=-1)"),
    "renormalised-gate": ("jnp.where(e[:, None] == jnp.arange(n), p[:, :n], 0.0)",
                          "jnp.where(e[:, None] == jnp.arange(n), 1.0, 0.0)"),
    "residual-scale": ("(res[0] * h + res[1]) + (res[2] * out + res[3])",
                       "h + (res[2] * out + res[3])"),
    "residual-bias": ("(res[0] * h + res[1]) + (res[2] * out + res[3])",
                      "(res[0] * h + res[1]) + res[2] * out"),
}


def turned(right: str, wrong: str):
    """The reference with one expression replaced."""
    path = ROOT / "cellbench/reference/zaya_cca.py"
    source = path.read_text()
    assert source.count(right) == 1, right
    spec = importlib.util.spec_from_loader(f"_zaya_turned_{abs(hash(wrong))}", None)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    exec(compile(source.replace(right, wrong), str(path), "exec"), mod.__dict__)
    return mod


@pytest.mark.parametrize("term", sorted(LEFT_OUT))
def test_a_term_left_out_is_not_the_reference(term):
    """The served model against the reference with one term of the layer
    dropped: far outside the rounding, so the comparison above would not
    pass a port that left it out (with this seed's balancing bias a pick
    flips in a layer or two: enough)."""
    model, params = build()
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, _ = served(model, params, toks, other)
    other_model = np.asarray(turned(*LEFT_OUT[term]).make_forward(TINY)(
        params, jnp.asarray(toks, jnp.int32), jnp.arange(80)))
    assert np.abs(got - other_model).max() > 30 * ROUNDING, term


def test_the_skip_output_adds_exactly_nothing_and_held_is_the_reference():
    """One expert sublayer on random rows: ``grouped_expert_dispatch`` over
    the router's 5 outputs with experts 0-3 held equals the reference's
    gate-weighted sum over every expert, and a row that picked the skip
    output gets exactly (a_r h + b_r) + b_o: the expert term is 0.0."""
    model, params = build()
    layers = params["layers"]
    lp = jax.tree.map(lambda a: a[1], layers)
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 96, 64), jnp.float32)
    r0 = jnp.zeros((96, 32), jnp.float32)
    valid = jnp.ones((1, 96), bool).at[0, 90:].set(False)
    y, r, counted = model._experts(layers, lp, 1, h, r0, valid)
    x = ref.rms_norm(h[0], lp["mlp_norm"], TINY["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        p, e, r_ref = ref.route(x, r0, lp, TINY)
        out = ref.experts(x, p, e, lp)
        expect = ref.merge(h[0], out, lp["mlp_res"])
    assert np.abs(np.asarray(y[0] - expect)).max() < 1e-5
    assert np.abs(np.asarray(r - r_ref)).max() < 1e-5
    skipped = np.asarray(e) == 4
    assert 0 < skipped.sum() < 96
    res = np.asarray(lp["mlp_res"])
    bare = (res[0] * np.asarray(h[0]) + res[1]) + (res[2] * 0.0 + res[3])
    assert np.array_equal(np.asarray(y[0])[skipped], bare[skipped])
    assert np.all(np.asarray(out)[skipped] == 0.0)
    real = np.arange(96) < 90
    assert list(np.asarray(counted)) == [
        90, int((~skipped & real).sum()), 1,
        len(set(np.asarray(e)[~skipped])), int((skipped & real).sum())]


def test_the_router_s_state_carries_layer_0_s_into_layer_1_s_pick():
    """Exponential depth averaging: layer 1 routes on its own projection
    plus γ₁ ⊙ what layer 0 left.  Perturbing layer 0's state changes layer
    1's picks; with γ₁ = 0 it changes nothing."""
    model, params = build()
    layers = params["layers"]
    lp0, lp1 = (jax.tree.map(lambda a: a[i], layers) for i in (0, 1))
    x = jax.random.normal(jax.random.PRNGKey(7), (256, 64), jnp.float32)
    zeros = jnp.zeros((256, 32), jnp.float32)
    _, _, r0 = route(lp0, x, zeros, 1e-5)
    assert np.abs(np.asarray(r0)).max() > 0
    _, pick, r1 = route(lp1, x, r0, 1e-5)
    _, moved, _ = route(lp1, x, r0 + 3.0 * jnp.roll(r0, 1, axis=0), 1e-5)
    assert 0 < int((pick != moved).sum())
    # the state that goes on is this layer's own plus the carried one
    _, _, alone = route(lp1, x, zeros, 1e-5)
    assert np.allclose(np.asarray(r1 - alone),
                       np.asarray(lp1["router_eda"] * r0), atol=1e-6)
    still = dict(lp1, router_eda=jnp.zeros_like(lp1["router_eda"]))
    _, a, _ = route(still, x, r0, 1e-5)
    _, b, _ = route(still, x, 5.0 * r0, 1e-5)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("change,words", [
    ({"model_type": "llama"}, "model_type"),
    ({"layer_types": ["hybrid"] * 3}, "layer_types"),
    ({"layer_types": ["hybrid"] * 3 + ["hybrid_sliding"]}, "hybrid_sliding"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"attention_bias": True}, "attention_bias"),
    ({"lm_head_bias": True}, "lm_head_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"num_experts_per_tok": 2}, "num_experts_per_tok"),
    ({"num_key_value_heads": 4}, "num_key_value_heads"),
])
def test_from_hf_config_refuses_by_name_what_it_does_not_compute(change, words):
    with pytest.raises(NotImplementedError, match=words):
        ZayaConfig.from_hf_config({**TINY, **change})
