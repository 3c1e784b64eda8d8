"""cellbench/configs/jamba2-3b.json against the published code's own
properties and the seeded decay's spread: what ``assumed`` states, held."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from hybrid_linear_tiny import ROOT

CONFIG = json.loads((ROOT / "cellbench/configs/jamba2-3b.json").read_text())


def test_attention_layers_is_the_published_period_and_offset():
    """``attention_layers`` 2 beside ``attn_layer_period`` 14 and
    ``attn_layer_offset`` 7: the file's count, the program's layers and,
    where transformers is installed, ``JambaConfig.layers_block_type``."""
    cfg = HybridLinearConfig.from_hf_config(CONFIG)
    assert cfg.gqa_layers == (7, 21)
    assert CONFIG["attention_layers"] == len(cfg.gqa_layers)
    assert cfg.linear_layers == 26
    runs = HybridLinearModel(cfg).runs
    assert [(r.kind, r.count) for r in runs] == [
        ("linear", 7), ("gqa", 1), ("linear", 13), ("gqa", 1), ("linear", 6)]
    transformers = pytest.importorskip("transformers")
    keys = {k: v for k, v in CONFIG.items()
            if k in transformers.JambaConfig().to_dict() and k != "model_type"}
    published = transformers.JambaConfig(**keys)
    kinds = published.layers_block_type
    assert tuple(i for i, k in enumerate(kinds) if k == "attention") == (7, 21)
    assert set(published.layers_num_experts) == {1}
    assert published.mamba_dt_rank == cfg.gate_rank == 160


def test_the_state_is_9_318_400_bytes_a_slot_and_a_bf16_state_is_the_control():
    cfg = HybridLinearConfig.from_hf_config(CONFIG)
    assert cfg.state_shape == (16, 40, 128) and cfg.conv_width == 5120
    model = HybridLinearModel(cfg)
    assert model.state_bytes_per_slot() == 26 * (327_680 + 30_720) == 9_318_400
    control = HybridLinearModel(cfg, state_dtype=jnp.bfloat16)
    assert control.state_bytes_per_slot() == 26 * (163_840 + 30_720)
    cache = jax.eval_shape(lambda: control.init_kv_cache(8, 32, slots=2))
    assert cache["state"].dtype == jnp.bfloat16
    # the negative control runs the XLA forms: the kernels take float32
    assert control.state_update_impl()[0] == control.state_scan_impl()[0] == "xla"


def test_the_seeded_decay_remembers():
    """``assumed``: A_log = ln(1..16) a channel, D = 1, W_dt uniform in
    ±rank^-1/2 and b_dt the inverse softplus of a step log-uniform in
    [0.001, 0.1] — under which the per-token decay exp(Δ A) has its median
    over tokens in [0.9, 0.999] for more than half of the (channel, index)
    pairs and forgets in three tokens (under e^-1/3 a token) for under a
    quarter (the fast indices of the channels with the largest steps, as
    published): a state that forgets lets a broken chunk carry pass the
    check.  At the
    published rank and inner width, two layers."""
    hf = dict(CONFIG, num_hidden_layers=2, attn_layer_period=2,
              attn_layer_offset=1, vocab_size=256, hidden_size=2560,
              intermediate_size=64)
    cfg = HybridLinearConfig.from_hf_config(hf, dtype="float32")
    params = HybridLinearModel(cfg).init_params(jax.random.PRNGKey(7))
    lp = jax.tree.map(lambda a: a[0], params["groups"]["linear"])
    assert lp["a_log"].shape == (16, 5120) and lp["w_dt"].shape == (160, 5120)
    assert np.allclose(np.exp(np.asarray(lp["a_log"]))[:, 11],
                       np.arange(1, 17), rtol=1e-6)
    assert np.all(np.asarray(lp["d_skip"]) == 1)
    assert np.abs(np.asarray(lp["w_dt"])).max() <= 160 ** -0.5
    step0 = np.log1p(np.exp(np.asarray(lp["dt_bias"])))
    assert 0.001 <= step0.min() and step0.max() <= 0.1 * (1 + 1e-5)
    # δ behind its norm has unit RMS: 256 tokens of it
    delta = jax.random.normal(jax.random.PRNGKey(8), (256, 160), jnp.float32)
    delta = delta / jnp.sqrt(jnp.mean(delta * delta, axis=-1, keepdims=True))
    step = jax.nn.softplus(delta @ lp["w_dt"] + lp["dt_bias"])     # [T, I]
    decay = jnp.exp(step[:, None, :] * -jnp.exp(lp["a_log"]))      # [T, N, I]
    median = np.median(np.asarray(decay), axis=0)
    share = np.mean((median >= 0.9) & (median <= 0.999))
    assert 0.5 < share < 0.7, share
    assert np.mean(median < math.exp(-1 / 3)) < 0.25
    # ... and the slowest index of most channels carries a hundred tokens
    assert np.mean(median[0] > 0.99) > 0.4
