"""What the Jamba tests share: a tiny ``jamba`` configuration (14 layers,
layer 7 attending: ``m x7, A, m x6``; 4 : 1 heads, an inner width of 128 =
one row of lanes, float32), the model on seeded weights and the plain
reference of the benchmark (cellbench/reference/jamba_hybrid.py).  The engine
helpers are hybrid_linear_tiny's.  No test lives here (ROADMAP R1 (11))."""

import functools
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

import hybrid_linear_tiny as delta_toy
from hybrid_linear_tiny import ROOT
from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_jamba_hybrid_reference",
        ROOT / "cellbench/reference/jamba_hybrid.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
# float32 end to end: what is left between the program and the reference is
# the order of the sums (paged against dense attention, the convolution's
# carried tail against a padded one); the recurrence is the same token by
# token on both sides
ROUNDING = 2e-4

TINY = dict(
    model_type="jamba", vocab_size=128, hidden_size=64,
    intermediate_size=96, num_hidden_layers=14,
    num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=14, attn_layer_offset=7,
    expert_layer_period=2, expert_layer_offset=1,
    num_experts=1, num_experts_per_tok=1,
    mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
    mamba_conv_bias=True, mamba_proj_bias=False, use_mamba_kernels=False,
    hidden_act="silu", rms_norm_eps=1e-6, sliding_window=None,
    tie_word_embeddings=True, max_position_embeddings=4096)


def build(cfg: dict = TINY, seed: int = 0, **kw):
    model = HybridLinearModel(
        HybridLinearConfig.from_hf_config(cfg, dtype="float32"), **kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


def want(params, tokens, at, cfg: dict = TINY) -> np.ndarray:
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


worst_delta = functools.partial(delta_toy.worst_delta, cfg=TINY, want=want)
