"""What the seed and the configuration give ZAYA: a router whose picks are
spread over its outputs on every seed (or a step's time follows the seed),
vectors far enough from neutral that a dropped term shows, the tied head,
and the cell's ``EngineConfig`` as ``dynamo-tpu run`` would build it."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.zaya import (ROUTER_OUT_STD, ZayaConfig, ZayaModel,
                                    route)
from hybrid_linear_tiny import ROOT
from zaya_tiny import TINY, build

FILE = ROOT / "cellbench/configs/zaya1-8b.json"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_seeded_router_spreads_its_picks_over_all_17_outputs(seed):
    """At the published widths of the router (2,048 -> 256 -> 256 -> 256 ->
    17) on unit-variance rows, four layers deep: no output takes more than
    2.5 times or less than a quarter of an even share in any layer, the skip
    output among them, and the pick's probability is 0.2-0.5 — an expert
    sublayer that weighs, under a softmax that is not uniform.  64 rows
    touch 15 of 16 experts, as an even spread would (15.7)."""
    cfg = ZayaConfig.from_hf_config(dict(
        TINY, hidden_size=2048, router_hidden_size=256, num_experts=16,
        moe_intermediate_size=16, vocab_size=16), dtype="float32")
    params = ZayaModel(cfg).init_params(jax.random.PRNGKey(seed))
    r = jnp.zeros((4096, 256), jnp.float32)
    for li in range(4):
        lp = {k: v[li] for k, v in params["layers"].items()
              if k.startswith("router")}
        x = jax.random.normal(jax.random.PRNGKey(100 + li), (4096, 2048))
        p, pick, r = route(lp, x, r, 1e-5)
        share = np.bincount(np.asarray(pick), minlength=17) * 17 / 4096
        assert 0.25 < share.min() and share.max() < 2.5, (li, share)
        top = float(jnp.take_along_axis(p, pick[:, None], axis=-1).mean())
        assert 0.2 < top < 0.5, top
        touched = (1 - (1 - share[:16] / 17) ** 64).sum()
        assert touched > 15.0, touched
    w3 = np.asarray(params["layers"]["router_w3"])
    assert np.abs(w3.sum(axis=1)).max() < 1e-4          # centred over inputs
    assert 0.9 < w3.std() * 16 / ROUTER_OUT_STD < 1.1


def test_the_seeded_vectors_are_off_their_neutral_values():
    model, params = build()
    lp = params["layers"]
    for key in ("attn_res", "mlp_res"):
        res = np.asarray(lp[key])                       # [L, 4, Dm]
        assert np.allclose(res.mean(axis=(0, 2)), [1, 0, 1, 0], atol=0.01)
        assert np.all(res.std(axis=(0, 2)) > 0.01)
    assert np.asarray(lp["temp"]).std() > 0.02
    assert 0.3 <= float(lp["router_eda"].min()) and float(lp["router_eda"].max()) <= 0.7
    for key in ("router_down_b", "router_b1", "router_b2", "router_bias",
                "conv0_b", "conv1_b"):
        assert np.asarray(lp[key]).std() > 0.005, key
    assert lp["conv1_w"].shape == (4, 2, 10, 16, 16)
    assert lp["conv0_w"].shape == (4, 160, 2)
    assert lp["router_w3"].shape == (4, 32, 5)
    assert "lm_head" not in params


def test_tied_logits_are_the_embedding_turned_round():
    model, params = build()
    hidden = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64), jnp.float32)
    got = model.compute_logits(params, hidden)
    want = np.asarray(hidden) @ np.asarray(params["embed"]).T
    assert got.shape == (2, 5, 128) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < 1e-5


def test_the_cell_s_engine_config_is_pinned():
    """``serve`` -> flags -> ``dynamo-tpu run``'s namespace -> EngineConfig:
    batch 64 = the traffic's clients, blocks of 32, 4,096 positions, chunks
    of 512, 6,272 blocks; every other field the ``run`` default — no host
    blocks, no persistence, bf16 K/V, no speculation, the alternating
    scheduler with dispatch-ahead, prefix reuse asked for (and switched off
    by the engine for a model that keeps tails)."""
    from cellbench import server

    cfg = json.loads(FILE.read_text())
    assert cfg["serve"] == {"max_batch_size": 64, "block_size": 32,
                            "max_model_len": 4096,
                            "prefill_chunk_tokens": 512, "num_blocks": 6272}
    ecfg = server.engine_config(server.run_args(cfg["serve"]))
    assert (ecfg.max_batch_size, ecfg.block_size, ecfg.max_model_len,
            ecfg.prefill_chunk_tokens, ecfg.num_blocks) == (
        64, 32, 4096, 512, 6272)
    assert (ecfg.num_host_blocks, ecfg.kv_persist_dir, ecfg.cache_dtype,
            ecfg.spec_tokens, ecfg.sp_prefill_threshold,
            ecfg.prefill_token_budget, ecfg.unified_token_dispatch,
            ecfg.lookahead_dispatch) == (0, None, None, 0, 0, 0, False, False)
    assert ecfg.enable_prefix_reuse
    # worst case of the traffic + the check's four prompts and the null block
    assert 64 * (2048 + 1024) // 32 + 128 == cfg["serve"]["num_blocks"]


def test_the_file_states_its_cut_its_deployment_and_what_it_assumed():
    cfg = json.loads(FILE.read_text())
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 20
    assert set(cfg["layer_types"]) == {"hybrid"}
    assert "40" in cfg["reduced_why"] and "2 pipeline stages" in cfg["deployment"]
    assumed = " ".join(cfg["assumed"])
    for word in ("arXiv:2510.04476", "arXiv:2511.17127", "gelu", "temperature",
                 "skip", "depth averaging", "centred", "float8"):
        assert word in assumed + cfg["check_why"], word
    for key in ("serve_why", "check_why", "reduced_why"):
        assert "TODO" not in cfg[key] and len(cfg[key]) > 400, key
    assert set(cfg["check"]) == {"abs_tol", "share_within", "median_tol"}
    mc = ZayaConfig.from_hf_config(cfg)
    assert (mc.num_layers, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.conv_taps, mc.rotary_dim, mc.n_routed_experts,
            mc.router_hidden_size, mc.vocab_size) == (
        20, 8, 2, 128, (2, 2), 64, 16, 256, 262272)
