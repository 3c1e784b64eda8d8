"""DeepSeek-V2 (MLA + DeepSeekMoE) parity vs transformers, and engine
serving through the paged cache (models/deepseek.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.deepseek import (
    DeepseekConfig,
    DeepseekModel,
    convert_hf_state_dict,
)

BLOCK = 16


def _hf_model(q_lora=None, topk_method="greedy", n_group=1, topk_group=1,
              attn_impl="absorbed"):
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    torch.manual_seed(0)
    hf_cfg = DeepseekV2Config(
        vocab_size=96,
        hidden_size=64,
        intermediate_size=96,
        moe_intermediate_size=32,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        n_routed_experts=8,
        num_experts_per_tok=2,
        n_shared_experts=2,
        routed_scaling_factor=1.5,
        kv_lora_rank=16,
        q_lora_rank=q_lora,
        qk_nope_head_dim=32,
        qk_rope_head_dim=16,
        v_head_dim=32,
        topk_method=topk_method,
        n_group=n_group,
        topk_group=topk_group,
        norm_topk_prob=False,
        first_k_dense_replace=1,
        moe_layer_freq=1,
        max_position_embeddings=256,
        attention_bias=False,
        aux_loss_alpha=0.0,
    )
    hf = DeepseekV2ForCausalLM(hf_cfg).eval()
    cfg = DeepseekConfig.from_hf(hf_cfg)
    cfg.dtype = "float32"
    cfg.attn_impl = attn_impl
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    return hf, cfg, convert_hf_state_dict(sd, cfg)


def _paged_forward(model, params, token_ids):
    """Full-prompt forward through the paged cache (fresh blocks)."""
    s = len(token_ids)
    nb = -(-s // BLOCK) + 1
    cache = model.init_kv_cache(nb, BLOCK)
    toks = jnp.asarray([token_ids], jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    bt = jnp.arange(nb, dtype=jnp.int32)[None, :]
    slot = pos  # blocks 0.. in order
    hidden, _ = model.forward(
        params, toks, pos, cache, bt,
        jnp.asarray([s], jnp.int32), slot,
    )
    return np.asarray(model.compute_logits(params, hidden))[0]


@pytest.mark.parametrize("q_lora", [None, 24])
@pytest.mark.parametrize("attn_impl", ["absorbed", "expanded"])
def test_deepseek_v2_matches_hf(q_lora, attn_impl):
    """MLA (with and without query LoRA, absorbed-latent AND expanded
    cache forms) + DeepSeekMoE logits match transformers through the
    paged path."""
    torch = pytest.importorskip("torch")
    hf, cfg, params = _hf_model(q_lora=q_lora, attn_impl=attn_impl)
    model = DeepseekModel(cfg)
    prompt = [3, 17, 9, 41, 5, 88, 23, 7, 60, 11]
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0].numpy()
    got = _paged_forward(model, params, prompt)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_deepseek_group_limited_routing_matches_hf():
    """group_limited_greedy (DeepSeek-V2/V2-Chat routing) parity."""
    torch = pytest.importorskip("torch")
    hf, cfg, params = _hf_model(topk_method="group_limited_greedy",
                                n_group=4, topk_group=2)
    model = DeepseekModel(cfg)
    prompt = [2, 9, 33, 71, 15, 8]
    with torch.no_grad():
        want = hf(torch.tensor([prompt])).logits[0].numpy()
    got = _paged_forward(model, params, prompt)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_deepseek_serves_through_engine():
    """Greedy decode through EngineCore (continuous batching, paged
    cache) matches HF greedy generation."""
    torch = pytest.importorskip("torch")
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    hf, cfg, params = _hf_model()
    model = DeepseekModel(cfg)
    prompt = [5, 6, 7, 8, 9, 10, 11, 12]
    n = 8
    with torch.no_grad():
        out = hf.generate(
            torch.tensor([prompt]), max_new_tokens=n, do_sample=False,
            use_cache=True,
        )[0][len(prompt):].tolist()

    ecfg = EngineConfig(max_batch_size=2, max_model_len=128, block_size=BLOCK,
                        num_blocks=24)
    engine = EngineCore(model, params, ecfg, eos_token_ids=[])
    toks = []
    engine.submit(EngineRequest(
        request_id="d", prompt=prompt,
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=lambda o: toks.extend(o.token_ids),
    ))
    for _ in range(100):
        if not engine.step():
            break
    assert toks == out


@pytest.mark.parametrize("attn_impl", ["absorbed", "expanded"])
def test_deepseek_int8_kv_parity(attn_impl):
    """int8 QuantKvCache under MLA (VERDICT r4 next #5): the absorbed
    latent cache (ONE scale per token) and the expanded oracle both stay
    close to the f32 cache and agree on the greedy next token — int8 on
    top of the latent is what fits real DeepSeek shapes on 16GiB chips."""
    pytest.importorskip("torch")
    from dynamo_tpu.ops.kv_quant import is_quant

    hf, cfg, params = _hf_model(attn_impl=attn_impl)
    model = DeepseekModel(cfg)
    prompt = [3, 17, 9, 41, 5, 88, 23, 7, 60, 11]
    ref = _paged_forward(model, params, prompt)

    s = len(prompt)
    nb = -(-s // BLOCK) + 1
    cache = model.init_kv_cache(nb, BLOCK, dtype="int8")
    assert is_quant(cache)
    toks = jnp.asarray([prompt], jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    bt = jnp.arange(nb, dtype=jnp.int32)[None, :]
    hidden, cache2 = model.forward(
        params, toks, pos, cache, bt, jnp.asarray([s], jnp.int32), pos,
    )
    assert is_quant(cache2) and cache2.data.dtype == jnp.int8
    got = np.asarray(model.compute_logits(params, hidden))[0]
    assert int(np.argmax(got[-1])) == int(np.argmax(ref[-1]))
    np.testing.assert_allclose(got, ref, atol=0.15, rtol=0.1)


def test_deepseek_engine_int8_kv():
    """EngineCore serving DeepSeek with cache_dtype=int8: decodes, and
    the early greedy tokens match the f32-cache engine (the established
    int8-KV acceptance bar, test_kv_quant.py)."""
    pytest.importorskip("torch")
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
    from dynamo_tpu.ops.kv_quant import is_quant

    hf, cfg, params = _hf_model()
    model = DeepseekModel(cfg)
    prompt = [5, 6, 7, 8, 9, 10, 11, 12]

    def decode(cache_dtype):
        ecfg = EngineConfig(max_batch_size=2, max_model_len=128,
                            block_size=BLOCK, num_blocks=24,
                            cache_dtype=cache_dtype)
        engine = EngineCore(model, params, ecfg, eos_token_ids=[])
        if cache_dtype == "int8":
            assert is_quant(engine.cache)
        toks = []
        engine.submit(EngineRequest(
            request_id="d", prompt=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=8, ignore_eos=True),
            emit=lambda o: toks.extend(o.token_ids),
        ))
        for _ in range(100):
            if not engine.step():
                break
        return toks

    base = decode(None)
    quant = decode("int8")
    assert len(quant) == 8
    assert base[:4] == quant[:4], (base, quant)


def test_from_hf_rejects_unsupported_configs():
    """Anything this port would get silently wrong must raise loudly:
    yarn rope_scaling (needs mscale softmax correction), an unknown score
    or choice.  V3's routing (sigmoid scores, ``noaux_tc`` over one group or
    group-limited over biased scores, normalised top-k) is computed
    (``moe_route``) and accepted."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, qk_nope_head_dim=32,
                qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=16,
                q_lora_rank=None, intermediate_size=96)
    for bad in (
        {"rope_scaling": {"type": "yarn", "factor": 40}},
        {"topk_method": "aux_free_v9"},
        {"scoring_func": "tanh"},
        {"moe_layer_freq": 2},
    ):
        with pytest.raises(NotImplementedError):
            DeepseekConfig.from_hf({**base, **bad})
    v3 = DeepseekConfig.from_hf({
        **base, "topk_method": "noaux_tc", "norm_topk_prob": True,
        "scoring_func": "sigmoid"})
    assert (v3.topk_method, v3.scoring_func, v3.norm_topk_prob) == (
        "noaux_tc", "sigmoid", True)
    grouped = DeepseekConfig.from_hf({
        **base, "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4})
    assert (grouped.n_group, grouped.topk_group) == (8, 4)
    assert DeepseekConfig.from_hf(base).qk_head_dim == 48


def test_deepseek_dir_loads_through_cli_builder(tmp_path):
    """A DeepSeek HF directory is detected by architecture and loads
    through the standard checkpoint path into a DeepseekModel — the
    family is reachable from `dynamo-tpu run/serve`, not only from
    Python."""
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.cli import _load_any_checkpoint
    from dynamo_tpu.models.loader import is_deepseek_dir

    hf, cfg, params_direct = _hf_model()
    d = tmp_path / "dsv2"
    d.mkdir()
    hf_cfg = hf.config.to_dict()
    hf_cfg["architectures"] = ["DeepseekV2ForCausalLM"]
    (d / "config.json").write_text(json.dumps(hf_cfg))
    save_file({k: v.detach().numpy() for k, v in hf.state_dict().items()},
              str(d / "model.safetensors"))

    assert is_deepseek_dir(d)
    model, params, quantized = _load_any_checkpoint(str(d), "float32")
    assert type(model).__name__ == "DeepseekModel"
    assert not quantized
    got = _paged_forward(model, params, [3, 17, 9, 41, 5])
    want = _paged_forward(DeepseekModel(cfg), params_direct,
                          [3, 17, 9, 41, 5])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
