"""Model correctness: our paged-attention JAX Llama vs transformers (torch CPU).

The oracle strategy: build a tiny random HF LlamaForCausalLM, load its
weights through our loader, and compare logits from (a) a full prefill and
(b) an incremental prefill+decode through the paged KV cache.  This pins
RoPE, GQA, RMSNorm, SiLU-MLP and the cache plumbing in one shot.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.models.loader import load_params_from_state_dict

BLOCK = 8
SEQ = 21
MAX_BLOCKS = 8


@pytest.fixture(scope="module")
def hf_model():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    model = LlamaForCausalLM(hf_cfg).eval()
    return hf_cfg, model


@pytest.fixture(scope="module")
def ours(hf_model):
    hf_cfg, model = hf_model
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    params = load_params_from_state_dict(cfg, model.state_dict())
    return cfg, LlamaModel(cfg), params


def _hf_logits(hf_model, tokens):
    import torch

    _, model = hf_model
    with torch.no_grad():
        out = model(torch.tensor([tokens]))
    return out.logits[0].float().numpy()


def _run_ours(model, params, tokens, *, chunks):
    """Run tokens through the paged path in the given chunk sizes."""
    cfg = model.config
    cache = model.init_kv_cache(MAX_BLOCKS, BLOCK)
    block_table = jnp.arange(MAX_BLOCKS, dtype=jnp.int32)[None, :]
    logits_out = []
    pos = 0
    for size in chunks:
        chunk = tokens[pos : pos + size]
        positions = jnp.arange(pos, pos + size, dtype=jnp.int32)[None, :]
        slot_idx = positions  # identity block table → slot == position
        hidden, cache = model.forward(
            params,
            jnp.asarray([chunk], dtype=jnp.int32),
            positions,
            cache,
            block_table,
            jnp.asarray([pos + size], dtype=jnp.int32),
            slot_idx,
        )
        logits_out.append(np.asarray(model.compute_logits(params, hidden))[0])
        pos += size
    return np.concatenate(logits_out, axis=0)


def test_full_prefill_matches_hf(hf_model, ours):
    cfg, model, params = ours
    tokens = list(np.random.RandomState(1).randint(0, 128, size=SEQ))
    ref = _hf_logits(hf_model, tokens)
    got = _run_ours(model, params, tokens, chunks=[SEQ])
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_chunked_prefill_and_decode_matches_hf(hf_model, ours):
    cfg, model, params = ours
    tokens = list(np.random.RandomState(2).randint(0, 128, size=SEQ))
    ref = _hf_logits(hf_model, tokens)
    # prefill in 2 chunks then decode token-by-token through the paged cache
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_qwen2_with_bias_matches_hf():
    """Qwen2 = Llama + QKV bias (+ typically tied embeddings)."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(3)
    hf_cfg = Qwen2Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        tie_word_embeddings=True,
    )
    hf = Qwen2ForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["Qwen2ForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert cfg.attention_bias and cfg.tie_word_embeddings
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(4).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_mixtral_moe_matches_hf():
    """Mixtral top-2 MoE through the paged path vs transformers."""
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    torch.manual_seed(5)
    hf_cfg = MixtralConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=256,
        tie_word_embeddings=False,
    )
    hf = MixtralForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["MixtralForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert cfg.is_moe and cfg.num_experts == 4
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(6).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[SEQ])
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_unsupported_architecture_rejected():
    with pytest.raises(ValueError, match="unsupported architecture"):
        ModelConfig.from_hf_config(
            {
                "architectures": ["GPTNeoXForCausalLM"],
                "vocab_size": 128,
                "hidden_size": 64,
                "intermediate_size": 128,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
            }
        )


def test_moe_forward_runs():
    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2)
    model = LlamaModel(cfg)
    import jax

    params = model.init_params(jax.random.PRNGKey(0))
    cache = model.init_kv_cache(4, BLOCK)
    toks = jnp.asarray([[1, 2, 3]], dtype=jnp.int32)
    positions = jnp.asarray([[0, 1, 2]], dtype=jnp.int32)
    hidden, cache2 = model.forward(
        params,
        toks,
        positions,
        cache,
        jnp.arange(4, dtype=jnp.int32)[None, :],
        jnp.asarray([3], dtype=jnp.int32),
        positions,
    )
    assert hidden.shape == (1, 3, cfg.hidden_size)
    assert np.isfinite(np.asarray(hidden)).all()


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_grouped_matches_dense(norm_topk, monkeypatch):
    """The grouped ragged_dot dispatch (default) must match the dense
    one-hot oracle (DYNAMO_MOE_DENSE=1) — same routing, same weighted
    combine, only the dispatch mechanics differ.  Includes empty experts
    (E=8, few tokens) so zero-sized groups are exercised."""
    import jax

    from dynamo_tpu.models.llama import _moe_mlp_dense, _moe_mlp_grouped

    cfg = ModelConfig.tiny(
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=norm_topk
    )
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer-0 slice
    x = jax.random.normal(
        jax.random.PRNGKey(4), (2, 5, cfg.hidden_size), jnp.float32
    )
    got = _moe_mlp_grouped(cfg, lp, x)
    want = _moe_mlp_dense(cfg, lp, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )
    # and the env switch routes through the dense oracle
    monkeypatch.setenv("DYNAMO_MOE_DENSE", "1")
    from dynamo_tpu.models.llama import _moe_mlp

    np.testing.assert_allclose(
        np.asarray(_moe_mlp(cfg, lp, x)), np.asarray(want), rtol=0, atol=0
    )


def test_moe_grouped_quantized_matches_dense():
    """Grouped dispatch over int8 QTensor experts matches the dense oracle
    on the same quantized weights."""
    import jax

    from dynamo_tpu.models.llama import _moe_mlp_dense, _moe_mlp_grouped

    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(5), quantized=True)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(
        jax.random.PRNGKey(6), (1, 7, cfg.hidden_size), jnp.float32
    )
    got = _moe_mlp_grouped(cfg, lp, x)
    want = _moe_mlp_dense(cfg, lp, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("routing", ["empty-experts", "one-expert"])
@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
def test_layer_indexed_dispatch_equals_sliced(layer, routing):
    """grouped_expert_dispatch over the whole stacked [L, E, ...] arrays and
    a layer index is the dispatch over that layer's slice, bit for bit in
    the serving dtype — the other layers' groups are empty and the grouped
    matmul skips them.  (In float32 the CPU's expansion of ragged_dot sums
    over group and row jointly, so there the two differ in the last ulp.)"""
    import jax

    from dynamo_tpu.models.llama import grouped_expert_dispatch

    n_layers, e, d, f, t, k = 5, 8, 32, 48, 3, 2   # 6 rows: experts stay empty
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    bf16 = lambda a: a.astype(jnp.bfloat16)
    w_gate = bf16(jax.random.normal(keys[0], (n_layers, e, d, f)) / d ** 0.5)
    w_up = bf16(jax.random.normal(keys[1], (n_layers, e, d, f)) / d ** 0.5)
    w_down = bf16(jax.random.normal(keys[2], (n_layers, e, f, d)) / f ** 0.5)
    x = bf16(jax.random.normal(keys[3], (t, d)))
    weights = jax.nn.softmax(jax.random.normal(keys[4], (t, k)), axis=-1)
    if routing == "one-expert":
        topi = jnp.full((t, k), e - 1, jnp.int32)
    else:
        topi = jax.random.randint(keys[5], (t, k), 0, e)

    li = jnp.int32(layer)   # traced in both, as the layer scan has it
    want = jax.jit(lambda li: grouped_expert_dispatch(
        x, weights, topi, e, w_gate[li], w_up[li], w_down[li], jax.nn.silu)
    )(li)
    got = jax.jit(lambda li: grouped_expert_dispatch(
        x, weights, topi, e, w_gate, w_up, w_down, jax.nn.silu, layer=li)
    )(li)
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    assert np.abs(want).max() > 0
    np.testing.assert_array_equal(got, want)


def _dispatch_layers_seen(monkeypatch) -> list:
    """The ``layer`` argument of every grouped_expert_dispatch call traced
    from here on: an index where ``forward`` closed over the expert stacks,
    None where the scan sliced them."""
    import dynamo_tpu.models.llama as llama

    seen = []
    dispatch = llama.grouped_expert_dispatch

    def spy(*a, layer=None):
        seen.append(layer)
        return dispatch(*a, layer=layer)

    monkeypatch.setattr(llama, "grouped_expert_dispatch", spy)
    return seen


def _tiny_qwen3_moe_forward(model, params, b, s):
    """Logits of one ``forward`` over fresh sequences: S > 1 takes the
    prefill fast path, S == 1 the decode path."""
    import jax

    toks = jax.random.randint(
        jax.random.PRNGKey(8), (b, s), 0, model.config.vocab_size)
    positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    tables = jnp.arange(b * 2, dtype=jnp.int32).reshape(b, 2)
    hidden, _ = model.forward(
        params, toks, positions, model.init_kv_cache(b * 2, BLOCK), tables,
        jnp.full((b,), s, jnp.int32), tables[:, :1] * BLOCK + positions,
        prefix_blocks=1 if s > 1 else None,
    )
    return np.asarray(model.compute_logits(params, hidden))


@pytest.mark.parametrize("shape", [(2, 5), (3, 1)], ids=["prefill", "decode"])
def test_qwen3_moe_forward_in_place_equals_sliced_scan(shape, monkeypatch):
    """A Qwen3-MoE ``forward`` that closes over the expert stacks gives the
    logits of the scan that slices them (the parent's), exactly, in bf16."""
    import jax

    import dynamo_tpu.models.llama as llama

    cfg = ModelConfig.tiny(
        num_layers=3, num_experts=8, num_experts_per_tok=2, qk_norm=True,
        norm_topk_prob=False, intermediate_size=48, dtype="bfloat16")
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(7))
    layers_seen = _dispatch_layers_seen(monkeypatch)
    got = _tiny_qwen3_moe_forward(model, params, *shape)
    assert layers_seen and all(li is not None for li in layers_seen)
    # the stored pytree is what it was: [L, E, Dm, F]
    assert params["layers"]["w_gate"].shape == (3, 8, cfg.hidden_size, 48)

    del layers_seen[:]
    monkeypatch.setattr(llama, "experts_in_place", lambda layers, tp: False)
    want = _tiny_qwen3_moe_forward(model, params, *shape)
    assert layers_seen and all(li is None for li in layers_seen)
    np.testing.assert_array_equal(got, want)


def _qkv_proj_folded(cfg, lp, x, b, s):
    """``_qkv_proj`` as it was until PR 38: the head reshape straight on
    the dot, which the TPU compiler folds into it (and then re-lays
    ``wq``/``wk`` out every layer).  The oracle of the test below."""
    from dynamo_tpu.models.llama import matmul, rms_norm

    dh, hq, hk = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q, k, v = matmul(x, lp["wq"]), matmul(x, lp["wk"]), matmul(x, lp["wv"])
    if cfg.attention_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hk, dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v.reshape(b, s, hk, dh)


@pytest.mark.parametrize("shape", [(2, 5), (3, 1)], ids=["prefill", "decode"])
@pytest.mark.parametrize("extra", [{}, {"qk_norm": True},
                                   {"attention_bias": True}],
                         ids=["plain", "qk_norm", "attention_bias"])
def test_projection_kept_out_of_the_head_reshape_is_bit_identical(
        extra, shape, monkeypatch):
    """``split_heads`` changes how the q/k dots are expressed, not what
    they compute: hidden states and the written cache equal the old
    expression's to the bit, in bf16."""
    import jax

    import dynamo_tpu.models.llama as llama

    model = LlamaModel(ModelConfig.tiny(num_layers=3, dtype="bfloat16",
                                        **extra))
    params = model.init_params(jax.random.PRNGKey(11))
    if extra.get("attention_bias"):   # init draws them zero
        keys = jax.random.split(jax.random.PRNGKey(12), 3)
        for name, key in zip(("bq", "bk", "bv"), keys):
            bias = params["layers"][name]
            params["layers"][name] = jax.random.normal(
                key, bias.shape, jnp.float32).astype(bias.dtype)

    def run():
        b, s = shape
        toks = jax.random.randint(
            jax.random.PRNGKey(8), (b, s), 0, model.config.vocab_size)
        positions = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
        tables = jnp.arange(b * 2, dtype=jnp.int32).reshape(b, 2)
        hidden, cache = model.forward(
            params, toks, positions, model.init_kv_cache(b * 2, BLOCK),
            tables, jnp.full((b,), s, jnp.int32),
            tables[:, :1] * BLOCK + positions,
            prefix_blocks=1 if s > 1 else None)
        return np.asarray(hidden, np.float32), np.asarray(cache, np.float32)

    got = run()
    monkeypatch.setattr(llama, "_qkv_proj", _qkv_proj_folded)
    want = run()
    assert np.abs(want[0]).max() > 0 and np.abs(want[1]).max() > 0
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_qtensor_experts_keep_the_sliced_form(monkeypatch):
    """int8 experts ride the scan's xs and dequantise after the slice (the
    choice is made on the leaf's type), and match the dense oracle."""
    import jax

    from dynamo_tpu.models.quant import QTensor

    cfg = ModelConfig.tiny(num_experts=4, num_experts_per_tok=2, qk_norm=True)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(5), quantized=True)
    assert isinstance(params["layers"]["w_gate"], QTensor)
    layers_seen = _dispatch_layers_seen(monkeypatch)
    got = _tiny_qwen3_moe_forward(model, params, 2, 5)
    assert layers_seen and all(li is None for li in layers_seen)
    monkeypatch.setenv("DYNAMO_MOE_DENSE", "1")
    want = _tiny_qwen3_moe_forward(model, params, 2, 5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_gemma2_matches_hf():
    """Gemma2 = GeGLU + (1+w) RMSNorm + embed scaling + sandwich norms +
    query_pre_attn_scalar + attn/final logit softcaps, all through the
    paged cache path."""
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config, Gemma2ForCausalLM

    torch.manual_seed(6)
    hf_cfg = Gemma2Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=256,
        query_pre_attn_scalar=24,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
        # HF eager attention applies softcap; sliding window off for the
        # tiny ctx (both layer types behave identically under SEQ < window)
        attn_implementation="eager",
    )
    hf = Gemma2ForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["Gemma2ForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert cfg.post_norms and cfg.rmsnorm_unit_offset and cfg.scale_embeddings
    assert cfg.hidden_activation == "gelu_tanh"
    assert cfg.attn_logit_softcap == 50.0 and cfg.final_logit_softcap == 30.0
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(7).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[SEQ])
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)
    # incremental decode through the paged cache too
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_gemma1_matches_hf():
    """Gemma (v1): GeGLU + (1+w) norms + embed scaling, no softcaps."""
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig, GemmaForCausalLM

    torch.manual_seed(8)
    hf_cfg = GemmaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=256,
        hidden_activation="gelu_pytorch_tanh",
    )
    hf = GemmaForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["GemmaForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert not cfg.post_norms and cfg.rmsnorm_unit_offset
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(9).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_activation_mapping_strict():
    """'gelu' (original Gemma-1 configs) maps to tanh-GELU; unknown
    activations raise instead of silently running SiLU."""
    import pytest as _pytest

    base = dict(
        architectures=["GemmaForCausalLM"], vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=1, head_dim=16,
    )
    cfg = ModelConfig.from_hf_config({**base, "hidden_act": "gelu"},
                                     dtype="float32")
    assert cfg.hidden_activation == "gelu_tanh"
    with _pytest.raises(ValueError, match="unsupported hidden activation"):
        ModelConfig.from_hf_config({**base, "hidden_act": "relu"},
                                   dtype="float32")


def test_qwen3_qk_norm_matches_hf():
    """Qwen3 = Llama + per-head q/k RMSNorm (pre-RoPE), explicit head_dim."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen3Config, Qwen3ForCausalLM

    torch.manual_seed(10)
    hf_cfg = Qwen3Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=256,
        tie_word_embeddings=True,
    )
    hf = Qwen3ForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["Qwen3ForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert cfg.qk_norm and not cfg.attention_bias
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(11).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_phi3_fused_projections_match_hf():
    """Phi3 = Llama with fused qkv_proj / gate_up_proj weights (the loader
    splits them)."""
    torch = pytest.importorskip("torch")
    from transformers import Phi3Config, Phi3ForCausalLM

    torch.manual_seed(12)
    hf_cfg = Phi3Config(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rope_scaling=None,
        pad_token_id=0,  # default 32000 exceeds the tiny vocab
    )
    hf = Phi3ForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["Phi3ForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(13).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)
    # longrope configs are rejected loudly
    with pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(
            {**d, "rope_scaling": {"type": "longrope"}}, dtype="float32"
        )


def test_llama31_rope_scaling_matches_hf():
    """Llama-3.1-style llama3 rope_scaling — frequencies scaled per HF's
    _compute_llama3_parameters — verified logit-for-logit, including at
    positions past the pre-scaling regime."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(14)
    hf_cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 32},
    )
    hf = LlamaForCausalLM(hf_cfg).eval()
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    assert cfg.rope_scaling and cfg.rope_scaling["factor"] == 8.0
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    # 60 tokens: well past original_max_position_embeddings=32, so the
    # scaled low-frequency band actually matters
    tokens = list(np.random.RandomState(15).randint(0, 128, size=60))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[32, 16] + [1] * 12)
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)


def test_rope_scaling_linear_and_rejects_unknown():
    base = dict(
        architectures=["LlamaForCausalLM"], vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=1,
    )
    cfg = ModelConfig.from_hf_config(
        {**base, "rope_scaling": {"rope_type": "linear", "factor": 2.0}},
        dtype="float32",
    )
    assert cfg.rope_scaling["factor"] == 2.0
    from dynamo_tpu.models.llama import rope_inv_freq

    import numpy as np_
    plain = np_.asarray(rope_inv_freq(16, 10000.0))
    lin = np_.asarray(rope_inv_freq(16, 10000.0, cfg.rope_scaling))
    np_.testing.assert_allclose(lin, plain / 2.0, rtol=1e-6)

    import pytest as _pytest
    with _pytest.raises(ValueError, match="rope_scaling"):
        ModelConfig.from_hf_config(
            {**base, "rope_scaling": {"rope_type": "yarn", "factor": 2.0}},
            dtype="float32",
        )


def test_qwen3_moe_matches_hf():
    """Qwen3-MoE: qk-norm attention + per-expert gate/up/down naming +
    norm_topk_prob routing, through the paged path vs transformers."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    torch.manual_seed(16)
    hf_cfg = Qwen3MoeConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        moe_intermediate_size=48,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        num_experts=4,
        num_experts_per_tok=2,
        norm_topk_prob=True,
        max_position_embeddings=256,
        tie_word_embeddings=True,
    )
    hf = Qwen3MoeForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["Qwen3MoeForCausalLM"]
    cfg = ModelConfig.from_hf_config(d, dtype="float32")
    assert cfg.is_moe and cfg.qk_norm and cfg.intermediate_size == 48
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(17).randint(0, 128, size=SEQ))
    import torch as _t

    with _t.no_grad():
        ref = hf(_t.tensor([tokens])).logits[0].float().numpy()
    got = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * (SEQ - 16))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)
    # non-uniform sparse stacks are rejected loudly
    with pytest.raises(ValueError, match="sparse"):
        ModelConfig.from_hf_config({**d, "mlp_only_layers": [0]},
                                   dtype="float32")


def test_qwen_max_window_layers_gate():
    """Qwen sliding-window gating (ADVICE r5): HF windows only layers >=
    max_window_layers, and the HF DEFAULT for an absent key is nonzero
    (e.g. 28 for Qwen2) — so use_sliding_window without the key must take
    the warn-and-full-attention path, NOT a uniform window.  Only an
    EXPLICIT max_window_layers: 0 means every layer is windowed."""
    base = dict(
        architectures=["Qwen2ForCausalLM"], vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=256,
        sliding_window=16, use_sliding_window=True,
    )
    # key absent → HF default (nonzero): full attention, window dropped
    assert ModelConfig.from_hf_config(dict(base),
                                      dtype="float32").sliding_window is None
    # nonzero boundary → same non-uniform treatment
    assert ModelConfig.from_hf_config({**base, "max_window_layers": 2},
                                      dtype="float32").sliding_window is None
    # explicit 0 → uniform window over all layers: honored exactly
    assert ModelConfig.from_hf_config({**base, "max_window_layers": 0},
                                      dtype="float32").sliding_window == 16
    # gate off → window ignored regardless
    assert ModelConfig.from_hf_config(
        {**base, "use_sliding_window": False, "max_window_layers": 0},
        dtype="float32").sliding_window is None


def test_mistral_sliding_window_matches_hf():
    """EXACT sliding-window attention (Mistral): a window SMALLER than
    the prompt must mask old keys exactly like HF's eager implementation
    — full prefill, chunked prefill, and token-by-token decode through
    the paged cache all agree."""
    torch = pytest.importorskip("torch")
    from transformers import MistralConfig, MistralForCausalLM

    torch.manual_seed(3)
    hf_cfg = MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, sliding_window=16,
        attn_implementation="eager",
    )
    hf = MistralForCausalLM(hf_cfg).eval()
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    assert cfg.sliding_window == 16
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    tokens = list(np.random.RandomState(5).randint(0, 128, size=40))
    with torch.no_grad():
        ref = hf(torch.tensor([tokens])).logits[0].float().numpy()
    # HF must actually be windowing, or this test proves nothing: the
    # full-attention run must DIFFER on positions past the window
    with torch.no_grad():
        hf_cfg_full = MistralConfig(**{**hf_cfg.to_dict(),
                                       "sliding_window": None})
        hf_full = MistralForCausalLM(hf_cfg_full).eval()
        hf_full.load_state_dict(hf.state_dict())
        ref_full = hf_full(torch.tensor([tokens])).logits[0].float().numpy()
    assert np.abs(ref[20:] - ref_full[20:]).max() > 1e-4, \
        "HF did not apply the sliding window; test is vacuous"

    got = _run_ours(model, params, tokens, chunks=[40])
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=5e-3)
    got2 = _run_ours(model, params, tokens, chunks=[9, 7] + [1] * 24)
    np.testing.assert_allclose(got2, ref, rtol=2e-2, atol=5e-3)


def test_sliding_window_noop_when_context_fits(monkeypatch):
    """The static no-op gate: when the context bound (M·Bs) fits inside
    the window, the dispatch must treat the call as FULL attention
    (window=None reaches the oracle — the property that keeps the flash
    kernels in play on TPU); when it can exceed the window, the window
    must reach the oracle."""
    import importlib

    import jax as _jax

    pa = importlib.import_module("dynamo_tpu.ops.paged_attention")
    seen = []
    real = pa.paged_attention

    def spy(*args, **kw):
        seen.append(kw.get("window"))
        return real(*args, **kw)

    monkeypatch.setattr(pa, "paged_attention", spy)
    cfg = ModelConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      max_position_embeddings=256, dtype="float32",
                      sliding_window=512)
    model = LlamaModel(cfg)
    params = model.init_params(_jax.random.PRNGKey(4))
    tokens = list(np.random.RandomState(6).randint(0, 128, size=24))
    # MAX_BLOCKS*BLOCK = 384 < 512: gate fires, oracle sees window=None
    _run_ours(model, params, tokens, chunks=[24])
    assert seen and set(seen) == {None}, seen

    seen.clear()
    cfg2 = ModelConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                       num_layers=2, num_heads=4, num_kv_heads=2,
                       max_position_embeddings=256, dtype="float32",
                       sliding_window=16)
    model2 = LlamaModel(cfg2)
    _run_ours(model2, params, tokens, chunks=[24])
    assert seen and set(seen) == {16}, seen


def test_mistral_sliding_window_engine_fast_prefill_matches_hf():
    """The ENGINE's chunked prefill takes the fast-prefill path
    (prefix_blocks buckets) — its fresh/prefix window masks are the
    subtlest code in the windowing diff and must match HF generate."""
    torch = pytest.importorskip("torch")
    from transformers import MistralConfig, MistralForCausalLM

    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    torch.manual_seed(11)
    hf_cfg = MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, sliding_window=16,
        attn_implementation="eager",
    )
    hf = MistralForCausalLM(hf_cfg).eval()
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())
    prompt = list(np.random.RandomState(8).randint(1, 128, size=30))
    n = 10
    with torch.no_grad():
        want = hf.generate(torch.tensor([prompt]), max_new_tokens=n,
                           do_sample=False,
                           use_cache=True)[0][len(prompt):].tolist()
    engine = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=16, num_blocks=24,
        prefill_chunk_tokens=16), eos_token_ids=[])
    toks = []
    engine.submit(EngineRequest(
        request_id="w", prompt=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=lambda o: toks.extend(o.token_ids)))
    for _ in range(100):
        if not engine.step():
            break
    assert toks == want, (toks, want)
