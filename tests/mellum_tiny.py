"""What the Mellum tests share: a tiny ``mellum`` configuration (two periods
of three window layers and a full one, window 24 over blocks of 8, 4 / 2 heads
of 16, eight experts top-2, float32; YaRN x4 over a trained context of 32 in
the full layers, so that the blended frequencies and the attention factor
move a prompt of 80 tokens), ``LlamaModel`` on seeded weights, the plain
reference of the benchmark (cellbench/reference/mellum_swa_moe.py), the
engine's layouts of a prefill chunk and a decode step by direct calls of
``forward``, and the four negative controls of scripts/mellum_longctx_check.py
as edits of the configuration the reference reads.  The engine helpers are
hybrid_linear_tiny's.  No test lives here (ROADMAP R1 (11))."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

import hybrid_linear_tiny
from hybrid_linear_tiny import ROOT, tokens_of  # noqa: F401 (the tests')
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

BS, WIDTH, NB, SLOTS = 8, 16, 64, 4      # block, table width, pool, batch
WINDOW = 24


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_mellum_swa_moe_reference",
        ROOT / "cellbench/reference/mellum_swa_moe.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()
# float32 on both sides: what is left between the program (paged attention by
# chunks and decode steps, experts sorted and grouped) and the reference (one
# full forward, every expert on every token) is the order of the sums.  Each
# negative control moves 1e-2 and more
ROUNDING = 3e-4

TINY = dict(
    architectures=["MellumForCausalLM"], model_type="mellum",
    vocab_size=128, hidden_size=64, intermediate_size=256,
    num_hidden_layers=8,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    attention_bias=False, hidden_act="silu", moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4, "beta_fast": 4, "beta_slow": 1,
                           "original_max_position_embeddings": 32,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    sliding_window=WINDOW, use_sliding_window=True, max_window_layers=0,
    tie_word_embeddings=False, rms_norm_eps=1e-6,
    max_position_embeddings=4096)

# the reference a port that got one thing wrong would agree with: each must
# be far from the served model on a prompt past the window
CONTROLS = {
    "every-layer-full": dict(sliding_window=1 << 20),
    "plain-rope-in-the-full-layers": dict(rope_parameters={
        **TINY["rope_parameters"],
        "full_attention": {"rope_type": "default", "rope_theta": 10000}}),
    "window-off-by-a-block": dict(sliding_window=WINDOW + BS),
}
KV_ROUND = (4, 3)       # float8 e4m3: the cache one precision down


def build(cfg: dict = TINY, seed: int = 0):
    model = LlamaModel(ModelConfig.from_hf_config(cfg, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(seed))


def want(params, tokens, at, cfg: dict = TINY, **kw) -> np.ndarray:
    return np.asarray(ref.make_forward(cfg, **kw)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


def logp(model, params, hidden):
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(params, hidden), axis=-1))


def chunk(model, params, cache, tokens, a, b, first_block, pad_to=None,
          prefix_blocks=None):
    """Tokens [a, b) of one sequence as the engine lays a prefill chunk out
    (padded to ``pad_to`` with slot -1; ``a`` on a block)."""
    n = pad_to or (b - a)
    bt = (first_block + np.arange(WIDTH, dtype=np.int32))[None]
    tok = np.zeros((1, n), np.int32)
    pos = np.zeros((1, n), np.int32)
    slots = np.full((1, n), -1, np.int32)
    at = np.arange(a, b)
    tok[0, :b - a], pos[0, :b - a] = tokens[a:b], at
    slots[0, :b - a] = bt[0, at // BS] * BS + at % BS
    pb = a // BS
    pb = 0 if pb == 0 else min(1 << (pb - 1).bit_length(), WIDTH)
    h, cache = model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray([b], jnp.int32), jnp.asarray(slots),
        prefix_blocks=pb if prefix_blocks is None else prefix_blocks)
    return logp(model, params, h[0, :b - a]), cache


def decode(model, params, cache, rows):
    """One decode step over the slot array: ``rows`` maps slot -> (tokens so
    far, first block, next token); the other slots are idle."""
    bt = np.zeros((SLOTS, WIDTH), np.int32)
    tok = np.zeros((SLOTS, 1), np.int32)
    pos = np.zeros((SLOTS, 1), np.int32)
    slot = np.full((SLOTS, 1), -1, np.int32)
    lens = np.zeros(SLOTS, np.int32)
    for i, (n, first_block, nxt) in rows.items():
        bt[i] = first_block + np.arange(WIDTH)
        tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
        slot[i, 0] = bt[i, n // BS] * BS + n % BS
    h, cache = model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray(lens), jnp.asarray(slot))
    return logp(model, params, h[:, 0]), cache


def engine(model, params, **kw):
    """hybrid_linear_tiny's engine at this toy's geometry."""
    return hybrid_linear_tiny.engine(model, params, **{
        "max_batch_size": SLOTS, "max_model_len": WIDTH * BS,
        "block_size": BS, "num_blocks": NB, **kw})
