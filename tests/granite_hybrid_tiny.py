"""What the granite-hybrid tests share: a tiny ``granitemoehybrid``
configuration (``m m A m m m``, SSD chunks of 16, float32), the model on
seeded weights and the plain reference of the benchmark
(cellbench/reference/granite_hybrid.py).  The engine helpers are
hybrid_linear_tiny's.  No test lives here (ROADMAP R1 (11))."""

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
        "_granite_hybrid_reference",
        ROOT / "cellbench/reference/granite_hybrid.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
# float32 end to end, and logits divided by ``logits_scaling`` 16: the order
# of the sums leaves ~1e-6, a tenth of what hybrid_linear_tiny.ROUNDING allows
# the delta-rule toy; one attending layer's softmax scale moves 7e-3
ROUNDING = 2e-4

TINY = dict(
    model_type="granitemoehybrid", vocab_size=128, hidden_size=64,
    num_hidden_layers=6,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, attention_bias=False,
    attention_multiplier=0.1, position_embedding_type="nope",
    mamba_n_heads=4, mamba_d_head=32, mamba_d_state=16, mamba_n_groups=1,
    mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=16,
    mamba_conv_bias=True, mamba_proj_bias=False,
    intermediate_size=32, shared_intermediate_size=48,
    num_local_experts=4, num_experts_per_tok=3,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
    tie_word_embeddings=True, hidden_act="silu",
    normalization_function="rmsnorm", rms_norm_eps=1e-5,
    max_position_embeddings=4096,
    expert_parallel={"chips": 2, "router_experts": 8, "first_expert": 4})


def build(cfg: dict = TINY, seed: int = 0, **kw):
    model = HybridLinearModel(
        HybridLinearConfig.from_hf_config(cfg, dtype="float32"), **kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


def want(params, tokens, at, cfg: dict = TINY) -> np.ndarray:
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


worst_delta = functools.partial(delta_toy.worst_delta, cfg=TINY, want=want)
