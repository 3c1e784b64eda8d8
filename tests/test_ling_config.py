"""``HybridLinearConfig.from_hf_config`` on the ``ling_hybrid_mla`` keys: what
it refuses by name, the benchmark's configuration file against the catalog's
row and the program's own shapes, and how the seeded decay is spread under
the lower-bound gate."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from hybrid_linear_tiny import ROOT
from ling_tiny import TINY, build

CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
FILE = ROOT / "cellbench/configs/ling-3.0-flash-ep4.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "first_k_dense_replace"]


@pytest.mark.parametrize("change,words", [
    ({"use_nGPT": True}, "use_nGPT"),
    ({"scale_router_input": True}, "scale_router_input"),
    ({"value_norm": True}, "value_norm"),
    ({"up_proj_norm": True}, "up_proj_norm"),
    ({"mtp_use_kda": True}, "mtp_use_kda"),
    ({"use_mla_nope": True}, "use_mla_nope"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"no_kda_lora": False}, "no_kda_lora"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"use_qk_norm": False}, "use_qk_norm"),
    ({"linear_silu": False}, "linear_silu"),
    ({"moe_router_enable_expert_bias": False}, "moe_router_enable_expert_bias"),
    ({"score_function": "softmax"}, "score_function"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"group_norm_size": 4}, "group_norm_size"),
    ({"num_kv_heads_for_linear_attn": 2}, "num_kv_heads_for_linear_attn"),
    ({"rotary_dim": 16}, "rotary_dim"),
    ({"partial_rotary_factor": 1.0}, "partial_rotary_factor"),
    ({"expert_swiglu_limit_list": [0, 0, 0, 0, 4, 4]},
     "expert_swiglu_limit_list"),
    ({"share_expert_swiglu_limit_list": [0, 5, 0, 0, 0, 0]},
     "share_expert_swiglu_limit_list"),
])
def test_from_hf_config_refuses_by_name_what_it_does_not_compute(change, words):
    with pytest.raises(NotImplementedError, match=words):
        HybridLinearConfig.from_hf_config({**TINY, **change})


@pytest.mark.parametrize("change,words", [
    ({"kda_lower_bound": 0}, "kda_lower_bound"),
    ({"published_layers": [0, 1, 2]}, "published_layers"),
    ({"published_layers": [0, 2, 1, 3, 4, 5]}, "published_layers"),
    ({"n_group": 3}, "n_group"),
    ({"topk_group": 5}, "topk_group"),
    ({"expert_parallel": {"router_experts": 16, "first_expert": 14}},
     "not among"),
])
def test_from_hf_config_rejects_keys_that_contradict_each_other(change, words):
    with pytest.raises(ValueError, match=words):
        HybridLinearConfig.from_hf_config({**TINY, **change})


def test_the_configuration_file_is_the_published_model_cut_as_stated():
    """Every number of the catalog's row under its key but the four reduced;
    ``attention_layers`` against ``layer_group_size`` over the layers kept;
    the parameter count of the cut from the program's own shapes (5.23 B =
    10.46 GB); the state, the latent pool and the share of the chip."""
    cfg = json.loads(FILE.read_text())
    assert cfg["reduced"] == REDUCED
    if CATALOG.is_file():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "Ling-3.0-flash-VL")
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cfg[key] == value, key
        assert {k: row["config"][k] for k in REDUCED} == {
            "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184,
            "first_k_dense_replace": 2}
    kept = cfg["published_layers"]
    assert kept == [0, 6, 7, 8, 9, 10, 11] and len(kept) == cfg["num_hidden_layers"]
    period = cfg["layer_group_size"]
    assert cfg["attention_layers"] == sum((i + 1) % period == 0 for i in kept) == 1
    # a whole period, every kind in its published ratio, behind the dense one
    assert [(i + 1) % period == 0 for i in kept[1:]] == [False] * 5 + [True]
    assert (cfg["num_experts"], cfg["vocab_size"],
            cfg["first_k_dense_replace"]) == (128, 39296, 1)
    assert cfg["expert_parallel"] == {"chips": 4, "router_experts": 512,
                                      "first_expert": 0}
    assert cfg["vocab_parallel"] == {"slices": 4, "slice": 0}
    assert cfg["vocab_size"] * 4 == 157184 and cfg["num_experts"] * 4 == 512
    # two whole routing groups a chip
    assert cfg["num_experts"] == 2 * 512 // cfg["n_group"]
    mc = HybridLinearConfig.from_hf_config(cfg)
    assert (mc.attention, mc.gqa_layers, mc.dense_layers, mc.gate_rank,
            mc.decay_lower_bound, mc.beta_scale, mc.head_dim) == (
        "mla", (6,), 1, 0, -5.0, 1.0, 576)
    model = HybridLinearModel(mc)
    assert [(r.kind, r.count) for r in model.runs] == [
        ("linear_dense", 1), ("linear", 5), ("mla", 1)]
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    dm = 2560
    expert = 3 * dm * 768
    routed = expert + dm * 512 + 512 + dm + 128 * expert   # shared, router, bias, norm
    kda = (4 * dm * 4096 + 2 * dm * 4096 + 12288 * 4 + dm * 32 + 32 + 4096
           + 128 + dm)
    mla = (dm * 32 * 192 + dm * 576 + 512 + 512 * 32 * 256 + dm * 32
           + 4096 * dm + dm)
    dense = 3 * dm * 6144 + dm
    assert n == (kda + dense) + 5 * (kda + routed) + (mla + routed) \
        + 2 * 39296 * dm + dm
    assert 10.45e9 < 2 * n < 10.47e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"], slots=serve["max_batch_size"]))
    assert cache["latent"].shape == (1, serve["num_blocks"], 32, 640)
    assert cache["latent"].dtype == jnp.bfloat16
    assert cache["state"].shape == (6, 64, 32, 128, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (6, 64, 3, 12288)
    assert cache["moe_counts"].shape == (7, 1, 7)
    assert model.state_bytes_per_slot() == 6 * (2 * 2**20 + 3 * 12288 * 2)
    # the traffic's worst case: 64 x (2,048 + 1,024) tokens
    assert serve["num_blocks"] * 32 >= 64 * 3072
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert 0.6 < (2 * n + held) / 16.9e9 < 0.75
    # the attention rooflines count the one layer that attends
    for name, kernel in (("decode", "mla_dense_decode"),
                         ("prefill", "mla_dense_prefill")):
        block = cfg["kernels"][f"kernel.{name}_attn_roofline"]
        assert block["pattern"] == "^" + kernel
        assert block["cost"] == kernel.replace("dense", "dense_layers")


def test_the_whole_model_is_the_published_size():
    """The layer equations give the published 125B-A5.5B: 124.4 B parameters,
    5.51 B active a token — the check that the shapes (full-rank KDA gates,
    direct W_q, one shared expert) are read right."""
    cfg = json.loads(FILE.read_text())
    whole = {k: v for k, v in cfg.items() if k != "published_layers"}
    whole.update(num_hidden_layers=42, num_experts=512, vocab_size=157184,
                 first_k_dense_replace=2,
                 expert_swiglu_limit_list=[0] * 42,
                 share_expert_swiglu_limit_list=[0] * 42)
    model = HybridLinearModel(HybridLinearConfig.from_hf_config(whole))
    assert len(model.config.gqa_layers) == 7 and model.config.dense_layers == 2
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert 124.3e9 < n < 124.5e9
    expert = 3 * 2560 * 768
    assert 5.50e9 < n - 40 * (512 - 8) * expert < 5.52e9
    # the published clamp in the last layers is refused by name
    with pytest.raises(NotImplementedError, match="expert_swiglu_limit_list"):
        HybridLinearConfig.from_hf_config(dict(
            whole, expert_swiglu_limit_list=cfg["expert_swiglu_limit_list"]))


def test_the_seeded_decay_remembers_under_the_lower_bound_gate():
    """alpha = exp(-5 sigmoid(exp(A_log) (x W_f + b_dt))) has its median over
    tokens in [0.9, 0.99] for at least a quarter of the key channels (a state
    that forgets in three tokens cannot show a broken chunk carry), at the
    published widths of the decay's parameters: 32 heads x 128 channels,
    full-rank from 2,560."""
    cfg = dict(TINY, hidden_size=2560, num_attention_heads=32, head_dim=128,
               qk_rope_head_dim=64, rotary_dim=64, num_hidden_layers=3,
               first_k_dense_replace=0,
               expert_swiglu_limit_list=[0] * 3,
               share_expert_swiglu_limit_list=[0] * 3)
    model, params = build(cfg)
    lp = jax.tree.map(lambda a: a[0], params["groups"]["linear"])
    x = jax.random.normal(jax.random.PRNGKey(9), (256, 2560), jnp.float32)
    a = (x @ lp["w_decay"] + lp["dt_bias"]).reshape(256, 32, 128)
    g = -5.0 * jax.nn.sigmoid(jnp.exp(lp["a_log"])[:, None] * a)
    assert float(g.min()) > -5.0 and float(g.max()) < 0.0
    alpha = np.median(np.exp(np.asarray(g)), axis=0).reshape(-1)
    share = np.mean((alpha >= 0.9) & (alpha <= 0.99))
    assert share >= 0.25, share
    assert np.mean(alpha < 0.5) < 0.2          # and few forget at once
    # the token moves the decay: it is a gate, not a constant
    assert np.std(np.asarray(g), axis=0).mean() > 0.01 * -np.mean(np.asarray(g))


def test_the_seeded_weights_of_the_models_that_were_there_are_the_parent_s():
    """The draws' order is the contract of ``init_params`` ("a new parameter
    goes after the ones that are there"): the delta rule's new switches and
    the split of a kind's parameters into mixer + feed-forward leave the
    three accepted toys' weights bit for bit what the parent commit drew
    (b719d4b, key 7, bf16), so the accepted cells' checks read what they
    read."""
    import hashlib

    import granite_hybrid_tiny
    import hybrid_linear_tiny
    import jamba_tiny

    parent = {"solar": "87c8c946672058be", "granite": "8363a52e3320feb1",
              "jamba": "94f27a52287070a1"}
    for name, toy in (("solar", hybrid_linear_tiny),
                      ("granite", granite_hybrid_tiny), ("jamba", jamba_tiny)):
        model = HybridLinearModel(
            HybridLinearConfig.from_hf_config(toy.TINY, dtype="bfloat16"))
        params = model.init_params(jax.random.PRNGKey(7))
        digest = hashlib.sha256()
        for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(params),
                                 key=lambda x: str(x[0])):
            digest.update(str(path).encode())
            digest.update(np.asarray(leaf).astype(np.float32).tobytes())
        assert digest.hexdigest()[:16] == parent[name], name
