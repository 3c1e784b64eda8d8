"""models/hybrid_linear.py with the selective recurrence (``jamba``) against
the benchmark's plain reference (cellbench/reference/jamba_hybrid.py) by
direct calls of ``forward``: prefill in chunks then decode through the cache,
the two branches the TPU takes (both kernels, interpreted) against the XLA
forms on the same cache, and what ``from_hf_config`` refuses."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import HybridLinearConfig
from hybrid_linear_tiny import tokens_of
from jamba_tiny import ROUNDING, TINY, build, want
# 80 tokens: 75 in chunks of 32, 32 and 11 in slot 2 behind a 20-token
# sequence in slot 0, then five decode steps beside it
from test_granite_hybrid_model import served
from test_hybrid_linear_model import chunk, decode, fresh_cache


@pytest.mark.parametrize("heads", [
    {}, {"num_attention_heads": 20, "hidden_size": 160}],
    ids=["4-to-1", "20-to-1"])
def test_prefill_in_chunks_then_decode_is_the_reference(heads):
    """float32 on both sides: what is left between the program (carried
    convolution, the state through the slot array, paged attention) and the
    reference (one full forward) is the order of the sums.  Logits, not
    tokens; the prompt crosses two chunks and goes on in decode."""
    cfg = {**TINY, **heads}
    model, params = build(cfg)
    assert [(r.kind, r.count) for r in model.runs] == [
        ("linear", 7), ("gqa", 1), ("linear", 6)]
    assert "lm_head" not in params
    for group in params["groups"].values():         # a plain MLP, no router
        assert {"mlp_gate", "mlp_up", "mlp_down"} <= set(group)
        assert not {"router", "w_gate", "shared_gate"} & set(group)
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    got, cache = served(model, params, toks, other)
    assert np.abs(got - want(params, toks, np.arange(80), cfg)).max() < ROUNDING
    counts = np.asarray(cache["moe_counts"])
    assert counts[0, 0, 4] == 13 * (20 + 75 + 2 * 5)       # tokens x layers
    assert counts[0, 0, 5] == 2 and counts[0, 0, 6] == 0   # resets, mismatches
    assert not counts[:, :, :4].any()                      # no expert layer
    assert list(np.asarray(cache["state_pos"])) == [25, 0, 80, 0]
    inner = 2 * cfg["hidden_size"]
    lanes = 128 if inner % 128 == 0 else inner
    assert cache["state"].shape == (13, 4, 16, inner // lanes, lanes)
    assert cache["conv"].shape == (13, 4, 3, inner)
    assert cache["kv"].shape[0] == 1
    assert cache["kv"].shape[-1] == cfg["hidden_size"] // cfg["num_attention_heads"]
    assert model.state_update_impl()[0] == "xla"
    assert model.state_scan_impl()[0] == "xla"


def test_both_kernels_are_the_xla_forms_through_the_model(monkeypatch):
    """The branches of ``_selective`` that the TPU takes — a chunk's scan and
    a decode's update where the state lies, by ops/pallas/selective_state.py,
    here interpreted — against slice / XLA form / set on the same cache: the
    live rows' log-probabilities, state and ``conv``, the idle slots bit for
    bit, ``state_pos`` and the counts equal.  An inner width of 1,024: eight
    rows of lanes, what the kernels tile."""
    from dynamo_tpu.models import hybrid_linear
    from dynamo_tpu.ops.pallas.selective_state import state_scan, state_update

    model, params = build(dict(TINY, mamba_expand=16, num_hidden_layers=4,
                               attn_layer_period=4, attn_layer_offset=1))
    assert model.state_update_impl() == ("xla", "backend is cpu")
    toks = tokens_of(40, 4)
    start = fresh_cache(model)
    # slot 1 holds what a finished request left; slot 3 starts at position 0
    start["state"] = start["state"].at[:, 1].set(7.0)
    start["state"] = start["state"].at[:, 2].set(-3.0)     # fresh: not read

    def run(cache):
        got = []
        for a, b, pad in ((0, 16, None), (16, 27, 16)):
            lp, cache = chunk(model, params, cache, toks, a, b, 2, 1, pad)
            got.append(lp)
        lp, cache = decode(model, params, cache, {
            2: (27, 1, toks[27]), 3: (0, 30, toks[0])})
        return np.concatenate([*got, lp[[2, 3]]]), cache

    want_lp, want_cache = run(jax.tree.map(jnp.array, start))
    monkeypatch.setattr(model, "state_update_impl", lambda: ("pallas", "test"))
    monkeypatch.setattr(model, "state_scan_impl", lambda: ("pallas", "test"))
    monkeypatch.setattr(hybrid_linear, "selective_state_update",
                        functools.partial(state_update, interpret=True))
    monkeypatch.setattr(hybrid_linear, "selective_state_scan",
                        functools.partial(state_scan, interpret=True))
    got_lp, got_cache = run(jax.tree.map(jnp.array, start))
    assert np.abs(got_lp - want_lp).max() < 1e-4
    got_s, want_s = (np.asarray(c["state"]) for c in (got_cache, want_cache))
    assert np.abs(got_s[:, [2, 3]] - want_s[:, [2, 3]]).max() < 1e-5
    assert np.array_equal(got_s[:, [0, 1]], np.asarray(start["state"])[:, [0, 1]])
    assert np.abs(np.asarray(got_cache["conv"])
                  - np.asarray(want_cache["conv"])).max() < 1e-4
    for leaf in ("state_pos", "moe_counts"):
        assert np.array_equal(np.asarray(got_cache[leaf]),
                              np.asarray(want_cache[leaf]))


@pytest.mark.parametrize("change,words", [
    ({"num_experts": 16}, "num_experts"),
    ({"num_experts": None}, "num_experts"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
])
def test_from_hf_config_refuses_by_name_what_it_does_not_serve(change, words):
    cfg = {k: v for k, v in {**TINY, **change}.items() if v is not None}
    with pytest.raises(NotImplementedError, match=words):
        HybridLinearConfig.from_hf_config(cfg)


def test_the_unified_decoder_names_the_class_that_serves_jamba():
    from dynamo_tpu.models.config import ModelConfig

    with pytest.raises(ValueError, match="hybrid_linear:HybridLinearModel"):
        ModelConfig.from_hf_config(
            {**TINY, "architectures": ["JambaForCausalLM"]})
