"""``ModelConfig.from_hf_config`` on the Mellum2 keys: the catalog's config
loads, ``layer_types`` gives the period the model scans, each kind gets its
rope, the window is honoured as a window - and what it refuses, by name."""

import json
import logging
import math

import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig, layer_period
from dynamo_tpu.models.llama import LlamaModel, kind_rope, yarn_inv_freq
from hybrid_linear_tiny import ROOT
from mellum_tiny import TINY

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


def published() -> dict:
    try:
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog on this machine")
    return next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def test_the_catalogs_keys_load_whole():
    """All 28 layers: seven periods, the window a window, the experts'
    width the expert width, the family's q/k norm, a rope a kind."""
    row = published()
    cfg = ModelConfig.from_hf_config(row["config"])
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (28, 2304, 98304)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    assert cfg.intermediate_size == 896          # moe_intermediate_size
    assert cfg.norm_topk_prob and cfg.qk_norm and not cfg.tie_word_embeddings
    assert cfg.sliding_window == 1024 and cfg.window_layers == 21
    assert cfg.layer_types == PERIOD * 7 and cfg.period == PERIOD
    assert cfg.rope_theta == 500000 and cfg.rope_scaling is None
    assert set(cfg.rope_parameters) == {"sliding_attention", "full_attention"}
    assert cfg.rope_parameters["full_attention"]["rope_type"] == "yarn"
    # model_type alone names the family (the catalog has no architectures)
    assert "architectures" not in row["config"]


def test_the_benchmarks_file_is_the_catalogs_cut_to_two_periods():
    row = published()["config"]
    with open(ROOT / "cellbench/configs/mellum2-12b-a2.5b.json") as f:
        cut = json.load(f)
    for key, value in row.items():
        if key in cut["reduced"]:
            continue
        assert cut[key] == value, key
    assert cut["num_hidden_layers"] == 8
    assert cut["layer_types"] == row["layer_types"][:8]
    assert cut["mlp_layer_types"] == row["mlp_layer_types"][:8]
    cfg = ModelConfig.from_hf_config(cut, dtype=cut["dtype"])
    assert cfg.period == PERIOD and cfg.window_layers == 6


@pytest.mark.parametrize("types,period", [
    (["a", "a", "a", "b"] * 3, ("a", "a", "a", "b")),
    (["a"] * 5, ("a",)),
    (["a", "b"], ("a", "b")),
    (["b", "a", "a", "b", "a", "a"], ("b", "a", "a")),
])
def test_layer_types_give_the_period(types, period):
    assert layer_period(types) == period


def test_a_period_builds_the_ropes_of_its_kinds():
    """A sliding layer turns by plain RoPE; a full layer by YaRN's blended
    frequencies (pairs 0-18 kept, 35-63 divided by 16) with cos and sin times
    the config's attention factor = 0.1 ln 16 + 1."""
    model = LlamaModel(ModelConfig.from_hf_config(published()["config"]))
    plain, one = model.kind_ropes["sliding_attention"]
    yarn, factor = model.kind_ropes["full_attention"]
    base = 500000.0 ** (-np.arange(64) * 2.0 / 128)
    np.testing.assert_allclose(np.asarray(plain), base, rtol=1e-6)
    assert one == 1.0
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(np.asarray(yarn)[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(yarn)[35:], base[35:] / 16, rtol=1e-6)
    mid = np.asarray(yarn)[19:35] / base[19:35]
    assert np.all(np.diff(mid) < 0) and 1 / 16 < mid[-1] < mid[0] < 1
    np.testing.assert_allclose(
        np.asarray(yarn), np.asarray(yarn_inv_freq(128, 5e5, 16, 8192)))
    # without the key the factor is YaRN's own
    rope = dict(published()["config"]["rope_parameters"]["full_attention"])
    del rope["attention_factor"]
    assert kind_rope(128, rope)[1] == pytest.approx(1.2772588722239782)


def edited(**kw) -> dict:
    return {**TINY, **kw}


@pytest.mark.parametrize("edit,named", [
    # six of eight layers: the last period is cut short
    (dict(num_hidden_layers=6, layer_types=TINY["layer_types"][:6],
          mlp_layer_types=["sparse"] * 6), "ragged last period"),
    (dict(mlp_layer_types=["sparse"] * 7 + ["dense"]), "mlp_layer_types"),
    (dict(rope_parameters={**TINY["rope_parameters"], "sliding_attention": {
        "rope_type": "longrope", "rope_theta": 10000}}), "unknown rope kind"),
    (dict(rope_parameters={"full_attention":
                           TINY["rope_parameters"]["full_attention"]}),
     "no rope for layers of kind 'sliding_attention'"),
    (dict(layer_types=["sliding_attention", "chunked_attention"] * 4),
     "unknown kind 'chunked_attention'"),
    (dict(layer_types=TINY["layer_types"][:4]), "names 4 layers"),
    (dict(max_window_layers=4), "max_window_layers=4"),
])
def test_what_is_refused_is_refused_by_name(edit, named):
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(edited(**edit))


def test_the_window_can_be_switched_off_and_the_kinds_keep_their_ropes():
    cfg = ModelConfig.from_hf_config(edited(use_sliding_window=False))
    assert cfg.sliding_window is None and cfg.window_layers == 0
    assert cfg.period == tuple(TINY["layer_types"][:4])


def test_the_other_interleaves_keep_their_behaviour_and_warning(caplog):
    """Gemma2 and a Qwen config with max_window_layers != 0 are still served
    with full attention, loudly; YaRN as a uniform rope_scaling is still
    refused; a stack of one kind has no period."""
    base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=2,
                num_key_value_heads=2)
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.models"):
        gemma = ModelConfig.from_hf_config(dict(
            base, architectures=["Gemma2ForCausalLM"], sliding_window=16))
        qwen = ModelConfig.from_hf_config(dict(
            base, architectures=["Qwen2ForCausalLM"], sliding_window=16,
            use_sliding_window=True, max_window_layers=1))
    assert gemma.sliding_window is None and qwen.sliding_window is None
    assert gemma.layer_types is None and gemma.period is None
    assert sum("served with full attention" in r.getMessage()
               for r in caplog.records) == 2
    with pytest.raises(ValueError, match="rope_scaling type 'yarn'"):
        ModelConfig.from_hf_config(dict(
            base, rope_scaling={"rope_type": "yarn", "factor": 4}))
    uniform = ModelConfig.from_hf_config(dict(
        base, architectures=["MistralForCausalLM"], sliding_window=16))
    assert uniform.sliding_window == 16 and uniform.window_layers == 2
    assert LlamaModel(uniform).period is None


def test_seq_parallel_prefill_is_refused_by_name_at_start_up():
    model = LlamaModel(ModelConfig.from_hf_config(TINY, dtype="float32"))
    assert not model.supports_seq_parallel
    with pytest.raises(NotImplementedError, match="layer_types"):
        model.forward_seq_parallel(None, np.zeros((1, 8), np.int32),
                                   np.zeros((1, 8), np.int32), None)
