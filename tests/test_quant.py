"""Int8 weight-only quantization: error bounds + serving parity.

VERDICT r2 ask #1: a quantized-vs-bf16 logit-error test gating the int8
path that makes Llama-3-8B fit (and get measured on) a single 16GiB chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.models.quant import (
    QTensor,
    align_specs,
    dequantize,
    matmul,
    quantize,
    quantize_params,
    take_rows,
)

BLOCK = 16


def test_quantize_roundtrip_error():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 128), jnp.float32)
    qt = quantize(w)
    assert qt.q.dtype == jnp.int8
    assert qt.scale.shape == (1, 128)
    err = np.abs(np.asarray(dequantize(qt, jnp.float32)) - np.asarray(w))
    # symmetric int8: error bounded by scale/2 per element
    assert (err <= np.asarray(qt.scale) / 2 + 1e-7).all()


def test_quantized_matmul_close():
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (8, 64), jnp.float32)
    w = jax.random.normal(kw, (64, 32), jnp.float32)
    exact = x @ w
    approx = matmul(x, quantize(w))
    rel = np.abs(np.asarray(approx - exact)) / (np.abs(np.asarray(exact)) + 1e-3)
    assert np.median(rel) < 0.02


def test_take_rows_dequant():
    w = jax.random.normal(jax.random.PRNGKey(2), (32, 16), jnp.float32)
    qt = quantize(w, channel_axes=(0,))
    idx = jnp.asarray([3, 7, 31])
    got = np.asarray(take_rows(qt, idx, jnp.float32))
    want = np.asarray(w)[np.asarray(idx)]
    assert np.abs(got - want).max() < np.asarray(qt.scale).max()


def _tiny_forward(model, params, cache):
    toks = jnp.asarray([[5, 9, 42, 7]], dtype=jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 3]], dtype=jnp.int32)
    hidden, _ = model.forward(
        params, toks, positions, cache,
        jnp.arange(4, dtype=jnp.int32)[None, :],
        jnp.asarray([4], dtype=jnp.int32),
        positions,
    )
    return model.compute_logits(params, hidden[:, -1])


@pytest.mark.parametrize("tie", [False, True])
def test_quantized_logits_close_and_greedy_agrees(tie):
    """Core accuracy gate: int8 logits track f32 logits closely enough
    that greedy decoding is (near-)unchanged on a tiny model."""
    cfg = ModelConfig.tiny(tie_word_embeddings=tie)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    qparams = model.quantize_params(params)

    logits = np.asarray(_tiny_forward(model, params, model.init_kv_cache(4, BLOCK)))
    qlogits = np.asarray(_tiny_forward(model, qparams, model.init_kv_cache(4, BLOCK)))

    spread = logits.max() - logits.min()
    assert np.abs(qlogits - logits).max() < 0.05 * spread
    assert int(qlogits.argmax(-1)[0]) == int(logits.argmax(-1)[0])


def test_quantize_params_shapes_and_selection():
    cfg = ModelConfig.tiny(num_experts=4)
    model = LlamaModel(cfg)
    qp = model.quantize_params(model.init_params(jax.random.PRNGKey(4)))
    lyr = qp["layers"]
    assert isinstance(lyr["wq"], QTensor)
    # per-layer (and per-expert) independent scales
    assert lyr["wq"].scale.shape == (cfg.num_layers, 1, cfg.num_heads * cfg.head_dim)
    assert lyr["w_up"].scale.shape == (cfg.num_layers, cfg.num_experts, 1, cfg.intermediate_size)
    assert isinstance(qp["embed"], QTensor)
    assert qp["embed"].scale.shape == (cfg.vocab_size, 1)
    # norms + router stay dense
    assert not isinstance(lyr["attn_norm"], QTensor)
    assert not isinstance(lyr["router"], QTensor)


def test_quantized_init_params_structure_matches():
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    dense = model.quantize_params(model.init_params(jax.random.PRNGKey(0)))
    direct = model.init_params(jax.random.PRNGKey(0), quantized=True)
    assert jax.tree_util.tree_structure(dense) == jax.tree_util.tree_structure(direct)


def test_align_specs_and_sharded_engine_step():
    """Quantized params shard over a real mesh and serve through the
    engine: align_specs must fan each PartitionSpec into (q, scale)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.utils.mesh import MESH_AXES, build_mesh

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    cfg = ModelConfig.tiny(num_kv_heads=4)  # 4 kv heads shard over model=2
    model = LlamaModel(cfg)
    qparams = model.quantize_params(model.init_params(jax.random.PRNGKey(5)))
    mesh = build_mesh((1, 2), MESH_AXES)

    specs = align_specs(qparams, model.partition_specs())
    assert isinstance(specs["layers"]["wq"], QTensor)
    assert specs["layers"]["wq"].q == P(None, None, "model")
    assert specs["layers"]["wq"].scale == P(None, None, "model")
    assert specs["layers"]["wo"].scale == P(None, None, None)  # reduced axis

    ecfg = EngineConfig(max_batch_size=2, max_model_len=64, block_size=BLOCK,
                        num_blocks=16)
    engine = EngineCore(model, qparams, ecfg, mesh=mesh, eos_token_ids=[])
    done = []
    engine.submit(EngineRequest(
        request_id="q1", prompt=[1, 2, 3, 4, 5],
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=6, ignore_eos=True),
        emit=lambda out: done.extend(out.token_ids),
    ))
    for _ in range(64):
        if not engine.step():
            break
    assert len(done) == 6
    assert all(0 <= t < cfg.vocab_size for t in done)
