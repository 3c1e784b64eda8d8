"""Mistral-Small-4-style model (models/glm_dsa.py, layers of kind ``none``):
dense latent attention over the cache held once, YaRN with the
position-dependent query scale, softmax-routed experts of which this chip
holds a share — against the plain float32 reference of the benchmark
(cellbench/reference/mistral4_mla.py), at a tiny size whose trained context
(32) the sequences (80) cross twice, so that the query scale takes three
values and YaRN's three bands are all present (pair 0 kept, pair 1 blended,
pairs 2-7 divided by the factor)."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
from dynamo_tpu.models.llama import yarn_inv_freq, yarn_mscale

ROOT = Path(__file__).resolve().parent.parent
BS, NB = 8, 48


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_mistral4_reference", ROOT / "cellbench/reference/mistral4_mla.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()

TINY = dict(
    model_type="mistral4", vocab_size=128, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=32, q_lora_rank=48,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=2,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1,
    norm_topk_prob=True, n_group=1, topk_group=1, first_k_dense_replace=0,
    rms_norm_eps=1e-6, rope_interleave=True, max_position_embeddings=4096,
    rope_parameters={
        "beta_fast": 32, "beta_slow": 1, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 32, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    expert_parallel={"chips": 4, "router_experts": 8, "first_expert": 2})


def build(cfg: dict = TINY, seed: int = 0):
    model = GlmDsaModel(GlmDsaConfig.from_hf_config(cfg, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(seed))


def tokens_of(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 128, n)


def want(cfg, params, tokens, at):
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


def logp(model, params, hidden):
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(params, hidden), axis=-1))


def table(first: int, n_tokens: int):
    return np.arange(first, first + -(-n_tokens // BS) + 1, dtype=np.int32)


def prefill(model, params, cache, tokens, blocks, chunks, width=14):
    """Prefill ``tokens`` in the given (start, end) chunks as the engine
    does: positions, slots, a power-of-two prefix bucket.  Returns the
    log-probabilities of every chunk's rows and the cache."""
    bt = np.zeros((1, width), np.int32)
    bt[0, :len(blocks)] = blocks
    out = []
    for a, b in chunks:
        pos = np.arange(a, b, dtype=np.int32)[None]
        slots = bt[0, pos // BS] * BS + pos % BS
        pb = a // BS
        pb = 0 if pb == 0 else 1 << (pb - 1).bit_length()
        h, cache = model.forward(
            params, jnp.asarray(tokens[None, a:b], jnp.int32),
            jnp.asarray(pos), cache, jnp.asarray(bt),
            jnp.asarray([b], jnp.int32), jnp.asarray(slots),
            prefix_blocks=min(pb, width))
        out.append(logp(model, params, h[0]))
    return np.concatenate(out), cache


def decode(model, params, cache, rows, width=14):
    """One decode step for (tokens so far, block table, next token) rows in
    a batch of 4, the rest idle."""
    bt = np.zeros((4, width), np.int32)
    tok = np.zeros((4, 1), np.int32)
    pos = np.zeros((4, 1), np.int32)
    slot = np.full((4, 1), -1, np.int32)
    lens = np.zeros(4, np.int32)
    for i, (seq, blocks, nxt) in enumerate(rows):
        n = len(seq)
        bt[i, :len(blocks)] = blocks
        tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
        slot[i, 0] = blocks[n // BS] * BS + n % BS
    return model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray(lens), jnp.asarray(slot))


def worst(got, ref_logp) -> float:
    return float(np.abs(got - ref_logp).max())


# the bf16 casts of the XLA attention form (queries, rows, probabilities)
# against float32: what rounding moves a log-probability by here (0.023-0.043
# over five token seeds).  Token seeds are ones that leave the router no near
# tie: where two experts lie within rounding of each other float32 and the
# program pick differently and that row moves by 0.4-1.0 (seeds 0, 1, 5),
# which is not what these tests are for.
ROUNDING = 0.06


@pytest.mark.parametrize("chunks", [
    [(0, 80)], [(0, 32), (32, 64), (64, 80)]], ids=["whole", "chunked"])
def test_prefill_matches_the_expanded_reference(chunks):
    """The absorbed form over the paged cache, in chunks, is the expanded
    per-head form of one full forward, at every position: below the trained
    context, across it and twice past it."""
    model, params = build()
    toks = tokens_of(80, seed=2)    # no router near-tie: see the module's note
    got, cache = prefill(model, params, model.init_kv_cache(NB, BS), toks,
                         table(1, 80), chunks)
    assert worst(got, want(TINY, params, toks, np.arange(80))) <= ROUNDING
    # every chunk ran 3 expert layers over its real tokens, top-2 of 8
    counts = np.asarray(cache["moe_counts"])[:, 0]
    assert (counts[:, 0] == 80 * 2).all() and (counts[:, 2] == len(chunks)).all()
    assert 0 < counts[:, 1].sum() < counts[:, 0].sum()


def test_decode_and_a_prefix_hit_alone_and_batched():
    """Decode through the cache, and a second prompt that reuses the first
    one's blocks for its first 64 tokens: the hit's logits are a cold
    prefill's, and a row's result does not depend on its batch."""
    model, params = build()
    a = tokens_of(72, seed=3)
    b = np.concatenate([a[:64], tokens_of(13, seed=8)])
    cache = model.init_kv_cache(NB, BS)
    ta = table(1, 96)
    _, cache = prefill(model, params, cache, a, ta, [(0, 72)])
    tb = np.concatenate([ta[:8], table(20, 32)])
    hit, cache = prefill(model, params, cache, b, tb, [(64, 77)])
    cold, _ = prefill(model, params, model.init_kv_cache(NB, BS), b,
                      table(30, 96), [(0, 77)])
    np.testing.assert_allclose(hit, cold[64:], atol=2e-3)
    assert worst(hit, want(TINY, params, b, np.arange(64, 77))) <= ROUNDING

    h, _ = decode(model, params, cache, [(a, ta, 5), (b, tb, 9)])
    assert np.isfinite(np.asarray(h)).all()
    got = logp(model, params, h[:2, 0])
    assert worst(got[:1], want(TINY, params, np.append(a, 5), [72])) <= ROUNDING
    assert worst(got[1:], want(TINY, params, np.append(b, 9), [77])) <= ROUNDING
    alone, _ = decode(model, params, cache, [(a, ta, 5)])
    np.testing.assert_allclose(
        logp(model, params, alone[:1, 0]), got[:1], atol=1e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 2 experts give, and the
    shared expert counted once, are the layer with all 8 experts resident —
    in the program and in the reference."""
    whole_cfg = dict(TINY, n_routed_experts=8, expert_parallel={
        "chips": 1, "router_experts": 8, "first_expert": 0})
    whole, wp = build(whole_cfg)
    g = wp["groups"]["sparse_none"]
    experts = ("w_gate", "w_up", "w_down")
    lp = jax.tree.map(lambda a: a[1],
                      {k: v for k, v in g.items() if k not in experts})
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64), jnp.float32)
    full, counted = whole._mlp(g, lp, 1, x, dense=False)
    # every pick is held, and 24 tokens' top-2 touch all 8 experts
    assert [int(n) for n in counted] == [48, 48, 1, 8]
    layer = ref.make_layer(whole_cfg)
    r_all, r_shared = layer({**lp, **{k: g[k][1] for k in experts}}, x[0])
    np.testing.assert_allclose(np.asarray(full[0]), r_all + r_shared,
                               atol=2e-4)
    total, total_ref, held = np.zeros((24, 64)), np.zeros((24, 64)), 0
    for first in (0, 2, 4, 6):
        cfg = dict(TINY, expert_parallel={
            "chips": 4, "router_experts": 8, "first_expert": first})
        part = GlmDsaModel(GlmDsaConfig.from_hf_config(cfg, dtype="float32"))
        gs = {**g, **{k: g[k][:, first:first + 2] for k in experts}}
        out, counted = part._mlp(gs, lp, 1, x, dense=False)
        r_part, r_sh = ref.make_layer(cfg)(
            {**lp, **{k: gs[k][1] for k in experts}}, x[0])
        np.testing.assert_allclose(r_sh, r_shared, atol=1e-6)
        total += np.asarray(out[0]) - np.asarray(r_sh)
        total_ref += np.asarray(r_part)
        held += int(counted[1])
    assert held == 48                       # each pick is held by one share
    np.testing.assert_allclose(total_ref, r_all, atol=2e-4)
    np.testing.assert_allclose(total + np.asarray(r_shared),
                               np.asarray(full[0]), atol=5e-4)


def test_yarn_frequencies_and_softmax_scale_for_the_published_keys():
    """Hand-computed for theta 10,000 over 64 dims, factor 128, trained
    context 8,192, beta 32 / 1: d(32) = 12.88 and d(1) = 24.92, so pairs
    0-12 keep f_j, 13-24 blend by (j - 12) / 13, 25-31 are f_j / 128."""
    inv = np.asarray(yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0),
                     np.float64)
    f = lambda j: 10000.0 ** (-2.0 * j / 64)
    assert inv[0] == pytest.approx(1.0)
    assert inv[12] == pytest.approx(f(12), rel=1e-6)            # lo: kept
    g = 1 / 13
    assert inv[13] == pytest.approx(f(13) * (1 - g) + f(13) / 128 * g,
                                    rel=1e-6)
    assert inv[25] == pytest.approx(f(25) / 128, rel=1e-6)      # hi: divided
    assert inv[31] == pytest.approx(f(31) / 128, rel=1e-6)
    assert inv[31] == pytest.approx(1.04181e-6, rel=1e-4)   # 1.3335e-4 / 128
    np.testing.assert_allclose(
        inv, np.asarray(ref.yarn(dict(
            rope_theta=10000, factor=128, beta_fast=32, beta_slow=1,
            original_max_position_embeddings=8192, mscale_all_dim=1), 64)[0]),
        rtol=1e-6)
    # sigma = 128^-1/2 · (0.1 ln 128 + 1)² = 0.08839 x 2.2058
    m = yarn_mscale(128.0, 1.0)
    assert m == pytest.approx(1.48520, abs=1e-5)
    published = dict(TINY, qk_nope_head_dim=64, qk_rope_head_dim=64,
                     rope_parameters=dict(TINY["rope_parameters"], factor=128,
                                          original_max_position_embeddings=8192))
    model = GlmDsaModel(GlmDsaConfig.from_hf_config(published))
    assert model.sm_scale == pytest.approx(0.194968, rel=1e-5)
    # lambda: 1.0 below 8,192, 1.0693 at 8,192, 1.1099 at 16,384
    lam = np.asarray(model._query_scale(
        jnp.asarray([[0, 8191, 8192, 16384, 32767]]))) / model.sm_scale
    np.testing.assert_allclose(
        lam[0], [1, 1, 1 + 0.1 * np.log(2), 1 + 0.1 * np.log(3),
                 1 + 0.1 * np.log(4)], rtol=1e-6)


def test_config_reader_takes_this_yarn_block_and_refuses_the_rest():
    cfg = GlmDsaConfig.from_hf_config(TINY)
    assert set(cfg.indexer_types) == {"none"} and not cfg.indexed
    assert (cfg.scoring_func, cfg.topk_method) == ("softmax", "greedy")
    assert cfg.yarn["factor"] == 8 and cfg.query_scale_beta == 0.1
    rope = TINY["rope_parameters"]

    def with_rope(**kw):
        return dict(TINY, rope_parameters={
            k: v for k, v in {**rope, **kw}.items() if v is not None})

    for bad in (with_rope(mscale=0.707),                 # m != m_all_dim
                with_rope(original_max_position_embeddings=None),
                with_rope(truncate=False),               # a key read elsewise
                with_rope(rope_type="longrope", type="longrope"),
                with_rope(rope_type="default", type="default"),  # beta alone
                dict(TINY, rope_scaling={"type": "yarn", "factor": 8}),
                dict(TINY, indexer_types=["none", "full", "shared"],
                     index_topk=16, index_n_heads=2, index_head_dim=8),
                dict(TINY, n_group=2)):
        with pytest.raises(NotImplementedError):
            GlmDsaConfig.from_hf_config(bad)
    # the Llama family still refuses YaRN
    from dynamo_tpu.models.config import ModelConfig

    with pytest.raises(ValueError, match="yarn"):
        ModelConfig.from_hf_config({
            "architectures": ["LlamaForCausalLM"], "vocab_size": 64,
            "hidden_size": 32, "num_hidden_layers": 1,
            "num_attention_heads": 2, "intermediate_size": 64,
            "rope_scaling": {"rope_type": "yarn", "factor": 8}})
