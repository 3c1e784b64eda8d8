"""What the Ling hybrid tests share: a tiny ``ling_hybrid_mla`` configuration
(two periods ``K K M`` of ``layer_group_size`` 3, the first layer ending in a
dense MLP, 16 experts in 4 routing groups of which 4 are held, float32), the
model on seeded weights and the plain reference of the benchmark
(cellbench/reference/ling_hybrid_mla.py).  The engine helpers are
hybrid_linear_tiny's.  No test lives here (ROADMAP R1 (11): files of <= 6
tests)."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from hybrid_linear_tiny import ROOT


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_ling_hybrid_mla_reference",
        ROOT / "cellbench/reference/ling_hybrid_mla.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()

TINY = dict(
    model_type="ling_hybrid_mla", vocab_size=128, hidden_size=64,
    num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=32, first_k_dense_replace=1,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=6000000,
    partial_rotary_factor=0.5, rotary_dim=8, use_mla_nope=False,
    layer_group_size=3, num_kv_heads_for_linear_attn=0, group_norm_size=1,
    linear_silu=True, short_conv_kernel_size=4, use_qk_norm=True,
    no_kda_lora=True, use_kda_lora=False, kda_safe_gate=True,
    kda_lower_bound=-5, gated_attention_proj_granularity_type="head_wise",
    use_nGPT=False, scale_router_input=False, value_norm=False,
    up_proj_norm=False, mtp_use_kda=False,
    num_experts=4, num_experts_per_tok=2, n_group=4, topk_group=2,
    score_function="sigmoid", moe_router_enable_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    expert_swiglu_limit_list=[0] * 5 + [4],
    share_expert_swiglu_limit_list=[0] * 6,
    max_position_embeddings=4096, tie_word_embeddings=False,
    expert_parallel={"chips": 4, "router_experts": 16, "first_expert": 4})
# ... with no clamp anywhere: the whole stack is kept
TINY["expert_swiglu_limit_list"] = [0] * 6


def build(cfg: dict = TINY, seed: int = 0, **kw):
    model = HybridLinearModel(
        HybridLinearConfig.from_hf_config(cfg, dtype="float32"), **kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


def want(params, tokens, at, cfg: dict = TINY) -> np.ndarray:
    """One program a length: op by op the reference is some hundred small
    compilations, a minute of a cold run's CPU."""
    return np.asarray(jax.jit(ref.make_forward(cfg))(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


WIDTH = 16          # blocks a row's table holds


def exact_attention(monkeypatch) -> None:
    """The XLA form of dense latent attention rounds the queries and the
    probabilities to bf16 whatever the cache holds (it is the kernels'
    oracle); in float32 instead, what is left between the program and the
    reference is the order of the sums."""
    import inspect

    from dynamo_tpu.ops import latent_cache

    src = inspect.getsource(latent_cache.dense_masked_attention).replace(
        "jnp.bfloat16", "jnp.float32")
    scope = dict(vars(latent_cache))
    exec(src, scope)
    monkeypatch.setattr(latent_cache, "dense_masked_attention",
                        scope["dense_masked_attention"])


_PROGRAMS: dict = {}


def forward(model):
    """``model.forward`` then the log-softmax of its logits, one program a
    shape (and a model: made after ``exact_attention``, it traces the
    patched form): (log-probabilities [B, S, V], cache)."""
    if id(model) not in _PROGRAMS:
        def run(params, *args, **kw):
            hidden, cache = model.forward(params, *args, **kw)
            return jax.nn.log_softmax(
                model.compute_logits(params, hidden), axis=-1), cache

        _PROGRAMS[id(model)] = (model, jax.jit(
            run, static_argnames=("prefix_blocks",)))
    return _PROGRAMS[id(model)][1]


def chunk(model, params, cache, tokens, a, b, slot, first_block, pad_to=None,
          bs=8, whole_table=False):
    """Tokens [a, b) of one sequence in engine slot ``slot``, as the engine
    lays a prefill chunk out (padded to ``pad_to`` with slot -1), its
    context the cached prefix's power-of-two bucket of blocks and its own —
    or, ``whole_table``, every block of the table (``prefix_blocks`` None:
    one program whatever the prefix)."""
    n = pad_to or (b - a)
    bt = (first_block + np.arange(WIDTH, dtype=np.int32))[None]
    tok = np.zeros((1, n), np.int32)
    pos = np.zeros((1, n), np.int32)
    slots = np.full((1, n), -1, np.int32)
    tok[0, :b - a] = tokens[a:b]
    pos[0, :b - a] = np.arange(a, b)
    slots[0, :b - a] = bt[0, np.arange(a, b) // bs] * bs + np.arange(a, b) % bs
    pb = a // bs
    pb = 0 if pb == 0 else 1 << (pb - 1).bit_length()
    lp, cache = forward(model)(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray([b], jnp.int32), jnp.asarray(slots),
        prefix_blocks=None if whole_table else min(pb, WIDTH),
        seq_slots=jnp.asarray([slot], jnp.int32))
    return np.asarray(lp[0, :b - a]), cache


def decode(model, params, cache, rows, n_slots=4, bs=8):
    """One decode step over the slot array: ``rows`` maps slot -> (tokens so
    far, first block, next token); the other slots are idle."""
    bt = np.zeros((n_slots, WIDTH), np.int32)
    tok = np.zeros((n_slots, 1), np.int32)
    pos = np.zeros((n_slots, 1), np.int32)
    slot = np.full((n_slots, 1), -1, np.int32)
    lens = np.zeros(n_slots, np.int32)
    for i, (n, first_block, nxt) in rows.items():
        bt[i] = first_block + np.arange(WIDTH)
        tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
        slot[i, 0] = bt[i, n // bs] * bs + n % bs
    lp, cache = forward(model)(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray(lens), jnp.asarray(slot))
    return np.asarray(lp[:, 0]), cache
