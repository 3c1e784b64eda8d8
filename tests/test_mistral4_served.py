"""The Mistral-Small-4-style model through ``EngineCore`` (chunked prefill,
decode with a dispatch in flight, a prefix hit, the counters), and the
negative controls: a program that leaves one piece of the attention's
mathematics out must fail the tolerance the real one passes
(tests/test_mistral4_mla.py: 0.06)."""

import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.models.glm_dsa as family
from test_mistral4_mla import (BS, NB, ROUNDING, TINY, build, prefill, table,
                               tokens_of, want, worst)


def rotate_half(x, positions, inv_freq):
    """RoPE over the pairs (i, i + d/2): the Llama layout, which
    ``rope_interleave`` true rules out."""
    ang = positions.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


def without_query_scale(model, monkeypatch):
    model.config.query_scale_beta = 0.0


def without_yarn_temperature(model, monkeypatch):
    model.sm_scale = model.config.qk_head_dim ** -0.5        # sigma without m²


def with_half_wise_pairs(model, monkeypatch):
    monkeypatch.setattr(family, "apply_rope_interleaved", rotate_half)


@pytest.mark.parametrize("damage", [
    without_query_scale, without_yarn_temperature, with_half_wise_pairs])
def test_a_program_that_leaves_it_out_fails_the_tolerance(damage, monkeypatch):
    model, params = build()
    toks = tokens_of(80, seed=2)
    want_logp = want(TINY, params, toks, np.arange(80))
    damage(model, monkeypatch)
    got, _ = prefill(model, params, model.init_kv_cache(NB, BS), toks,
                     table(1, 80), [(0, 32), (32, 64), (64, 80)])
    d = np.abs(got - want_logp).max(axis=-1)
    if damage is without_query_scale:
        # lambda is 1 below the trained context: those rows still agree
        assert d[:32].max() <= ROUNDING
    assert d.max() > 3 * ROUNDING, d.max()
    assert (d > ROUNDING).sum() >= 8


def test_engine_serves_it_with_chunks_decode_and_a_prefix_hit():
    """Through EngineCore: chunked prefill, the decode batch with a dispatch
    in flight, prefix reuse — and the counters that say so."""
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    model, params = build()
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=BS, num_blocks=NB,
        prefill_chunk_tokens=32), eos_token_ids=[])
    assert set(core.attention_impls()) == {"decode", "prefill"}
    doc = [int(t) for t in tokens_of(64, seed=2)]
    got: dict = {}

    def ask(name, question):
        got[name] = []
        core.submit(EngineRequest(
            request_id=name, prompt=doc + question,
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=6, ignore_eos=True),
            emit=lambda o, name=name: got[name].extend(o.token_ids)))
        while core.step():
            pass

    ask("first", [3, 4, 5, 6, 7])
    ask("again", [9, 8, 7])
    m = core.metrics()
    assert len(got["first"]) == 6 and len(got["again"]) == 6
    assert m["prompt_tokens_admitted_total"] == 69 + 67
    assert m["prompt_tokens_cached_total"] == 64
    assert core.prompt_tokens_computed == 69 + 3
    # a dense model attends to every position its decode rows can see
    assert m["attn_context_tokens_total"] == m["attn_selected_tokens_total"]
    assert m["attn_context_tokens_total"] >= 69 * m["decode_rows_dispatched_total"]
    # the expert layers' own counts, read back with the dispatches: top-2 a
    # real token and layer (72 prompt tokens computed, 5 decoded a request:
    # the sixth token is sampled, not run), 3 layers a dispatch
    tokens_run = core.prompt_tokens_computed + m["decode_rows_dispatched_total"]
    dispatches = m["prefill_dispatches_total"] + m["decode_dispatches_total"]
    assert m["moe_router_picks_total"] == 2 * 3 * tokens_run
    assert m["moe_expert_layer_calls_total"] == 3 * dispatches
    assert 0 < m["moe_held_picks_total"] < m["moe_router_picks_total"]
    # greedy tokens are the reference's argmax, teacher-forced
    seq = np.asarray(doc + [9, 8, 7] + got["again"])
    ref_logp = want(TINY, params, seq, np.arange(66, 66 + 6))
    assert (ref_logp.argmax(-1) == np.asarray(got["again"])).mean() >= 5 / 6


def test_block_movers_are_refused_for_a_cache_without_an_indexer_too():
    from dynamo_tpu.engine import EngineConfig, EngineCore

    model, params = build()
    for bad in (dict(num_host_blocks=8), dict(cache_dtype="int8"),
                dict(spec_tokens=2)):
        with pytest.raises(ValueError, match="block movers do not know"):
            EngineCore(model, params, EngineConfig(
                max_batch_size=2, max_model_len=64, block_size=BS,
                num_blocks=16, **bad), eos_token_ids=[])
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=BS, num_blocks=16),
        eos_token_ids=[])
    with pytest.raises(NotImplementedError, match="block movers do not know"):
        core.gather_blocks_np([1])
    # one row a token and layer, padded to whole lane groups (48 -> 128
    # float32 elements here); the three counts a layer are not the cache's
    assert core.counts.kv_bytes_per_token == 3 * 128 * 4
    assert core.counts.cache_layers == 3
