"""What the ZAYA tests share: a tiny ``zaya`` configuration (four layers,
8 / 2 heads of 16, rotary on half a head, four experts and the skip output,
float32), the model on seeded weights and the plain reference of the
benchmark (cellbench/reference/zaya_cca.py).  The engine helpers are
hybrid_linear_tiny's.  No test lives here (ROADMAP R1 (11))."""

import functools
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

import hybrid_linear_tiny as delta_toy
from hybrid_linear_tiny import ROOT
from dynamo_tpu.models.zaya import ZayaConfig, ZayaModel


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_zaya_cca_reference", ROOT / "cellbench/reference/zaya_cca.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
# float32 on both sides: what is left between the program (carried tails,
# paged attention, sorted experts) and the reference (one full forward) is
# the order of the sums.  A term of the layer left out moves 1e-2 and more
ROUNDING = 3e-4

TINY = dict(
    model_type="zaya", vocab_size=128, hidden_size=64, num_hidden_layers=4,
    layer_types=["hybrid"] * 4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, attention_bias=False, lm_head_bias=False, cca_time0=2,
    cca_time1=2, hidden_act="silu", moe_intermediate_size=32, num_experts=4,
    num_experts_per_tok=1, router_hidden_size=32, partial_rotary_factor=0.5,
    rope_parameters={
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    sliding_window=None, tie_word_embeddings=True, rms_norm_eps=1e-5,
    max_position_embeddings=4096)


def build(cfg: dict = TINY, seed: int = 0, **kw):
    model = ZayaModel(ZayaConfig.from_hf_config(cfg, dtype="float32"), **kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


def want(params, tokens, at, cfg: dict = TINY) -> np.ndarray:
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


worst_delta = functools.partial(delta_toy.worst_delta, cfg=TINY, want=want)
