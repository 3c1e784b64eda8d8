"""GLM-5.2-style model (models/glm_dsa.py): latent attention held once, the
sparse-attention indexer and IndexShare, one chip's share of the experts —
against the plain float32 reference of the benchmark
(cellbench/reference/glm_dsa.py), at a tiny size where ``index_topk`` (16)
is small against the contexts (48-96), so that the selection binds."""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.models.glm_dsa as glm
from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
from dynamo_tpu.ops import latent_cache

ROOT = Path(__file__).resolve().parent.parent
BS, NB = 8, 48


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_glm_dsa_reference", ROOT / "cellbench/reference/glm_dsa.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

TINY = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=32, q_lora_rank=48, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=2, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    index_n_heads=8, index_head_dim=16, index_topk=16,
    indexer_types=["full", "full", "shared", "shared", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    first_k_dense_replace=1, rms_norm_eps=1e-5, max_position_embeddings=512,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    expert_parallel={"router_experts": 8, "first_expert": 2})
# one index, computed from the embeddings alone and shared by every later
# layer: nothing upstream of the selection is rounded, so program and
# reference pick the same sets and every position can be held to rounding
ONE_INDEX = dict(
    TINY, num_hidden_layers=3, indexer_types=["full", "shared", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse"])


def _model(cfg: dict, seed: int = 0):
    model = GlmDsaModel(GlmDsaConfig.from_hf_config(cfg, dtype="float32"))
    return model, model.init_params(jax.random.PRNGKey(seed))


def _tokens(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 128, n)


def _want(cfg, params, tokens, at):
    padded = np.zeros(-(-len(tokens) // 32) * 32, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(padded), jnp.asarray(at)))


def _logp(model, params, hidden):
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(params, hidden), axis=-1))


def _table(first: int, n_tokens: int):
    return np.arange(first, first + -(-n_tokens // BS) + 1, dtype=np.int32)


def _prefill(model, params, cache, tokens, table, chunks, width=None):
    """Prefill ``tokens`` in the given (start, end) chunks as the engine
    does: positions, slots, a power-of-two prefix bucket.  Returns the
    log-probabilities of every chunk's rows and the cache."""
    width = width or len(table)
    bt = np.zeros((1, width), np.int32)
    bt[0, :len(table)] = table
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
        out.append(_logp(model, params, h[0]))
    return np.concatenate(out), cache


def _close(got, want, *, every=None, share=None):
    d = np.abs(got - want).max(axis=-1)
    if every is not None:
        assert d.max() <= every, d
    else:
        tol, frac = share
        assert np.median(d) <= tol / 4 and (d <= tol).mean() >= frac, (
            np.median(d), (d <= tol).mean(), d.max())


@pytest.fixture
def dense_chunks(monkeypatch):
    """A prefill chunk takes the masked form, as at serving's contexts (at
    the toy's 16 selected of under 100 positions the rule says gather)."""
    monkeypatch.setattr(glm, "masked_prefill_is_cheaper",
                        lambda context, topk: True)


# ------------------------------------------------ (a) program vs reference --
@pytest.mark.parametrize("chunks", [
    [(0, 80)], [(0, 32), (32, 64), (64, 80)]], ids=["whole", "chunked"])
def test_gather_prefill_matches_reference(chunks):
    model, params = _model(ONE_INDEX)
    assert not model.attends_masked(1, 80, 11, BS, 0)
    toks = _tokens(80)
    got, _ = _prefill(model, params, model.init_kv_cache(NB, BS), toks,
                      _table(1, 80), chunks)
    _close(got, _want(ONE_INDEX, params, toks, np.arange(80)), every=0.06)


@pytest.mark.parametrize("chunks", [
    [(0, 96)], [(0, 32), (32, 64), (64, 96)]], ids=["whole", "chunked"])
def test_dense_chunk_prefill_matches_reference(dense_chunks, chunks):
    model, params = _model(ONE_INDEX)
    toks = _tokens(96, seed=1)
    got, _ = _prefill(model, params, model.init_kv_cache(NB, BS), toks,
                      _table(1, 96), chunks)
    _close(got, _want(ONE_INDEX, params, toks, np.arange(96)), every=0.06)


def test_two_indexers_match_reference_but_for_boundary_flips():
    """With a second ``full`` layer the bf16 latent of layer 0 reaches the
    second indexer's input, and a position whose score lies at the top-k
    boundary can flip: most rows agree to rounding, a few differ more."""
    model, params = _model(TINY)
    toks = _tokens(80, seed=2)
    got, _ = _prefill(model, params, model.init_kv_cache(NB, BS), toks,
                      _table(1, 80), [(0, 48), (48, 80)])
    want = _want(TINY, params, toks, np.arange(80))
    _close(got[:16], want[:16], every=0.06)     # nothing to select from yet
    _close(got, want, share=(0.2, 0.7))


def test_decode_and_prefix_hit_alone_and_batched():
    """Decode through the cache, and a second prompt that reuses the first
    one's blocks for its first 64 tokens (a prefix hit is a block table
    that starts with another request's blocks), as rows of one batch."""
    model, params = _model(ONE_INDEX)
    a = _tokens(72, seed=3)
    # shares 64.  (The tail's seed is one whose tokens leave the router no
    # near tie: seed 4's fourth token has two experts 0.0002 apart in the
    # second expert layer, and which of them float32 picks moves that row by
    # 2.4 — not what this test is for.)
    b = np.concatenate([a[:64], _tokens(13, seed=8)])
    cache = model.init_kv_cache(NB, BS)
    ta = _table(1, 96)
    _, cache = _prefill(model, params, cache, a, ta, [(0, 72)], width=14)
    tb = np.concatenate([ta[:8], _table(20, 32)])
    got_b, cache = _prefill(model, params, cache, b, tb, [(64, 77)], width=14)
    _close(got_b, _want(ONE_INDEX, params, b, np.arange(64, 77)), every=0.06)

    def step(rows):
        """One decode step for (tokens so far, table, next token) rows,
        in a batch of 4 with the last row idle."""
        bt = np.zeros((4, 14), np.int32)
        tok = np.zeros((4, 1), np.int32)
        pos = np.zeros((4, 1), np.int32)
        slot = np.full((4, 1), -1, np.int32)
        lens = np.zeros(4, np.int32)
        for i, (seq, table, nxt) in enumerate(rows):
            n = len(seq)
            bt[i, :len(table)] = table
            tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
            slot[i, 0] = table[n // BS] * BS + n % BS
        return model.forward(
            params, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), jnp.asarray(lens), jnp.asarray(slot))

    h, cache2 = step([(a, ta, 5), (b, tb, 9)])
    assert np.isfinite(np.asarray(h)).all()
    got = _logp(model, params, h[:2, 0])
    _close(got[:1], _want(ONE_INDEX, params, np.append(a, 5), [72]), every=0.06)
    _close(got[1:], _want(ONE_INDEX, params, np.append(b, 9), [77]), every=0.06)
    alone, _ = step([(a, ta, 5)])
    np.testing.assert_allclose(
        _logp(model, params, alone[:1, 0]), got[:1], atol=1e-4)


@pytest.mark.parametrize("chunks", [[(0, 40)], [(0, 32), (32, 33)]],
                         ids=["prefill", "prefill-then-one-token"])
def test_projection_kept_out_of_the_head_reshape_is_bit_identical(
        chunks, monkeypatch):
    """``split_heads`` on ``q_b`` and the indexer's ``idx_wq_b`` is a change
    of how the dot is expressed: log-probabilities, both parts of the cache
    and the selection equal those of the old expression — the reshape
    straight on the dot — to the bit, in bf16."""
    model = GlmDsaModel(GlmDsaConfig.from_hf_config(TINY, dtype="bfloat16"))
    params = model.init_params(jax.random.PRNGKey(5))
    tokens, table = _tokens(40, seed=2), _table(1, 48)

    def run():
        logp, cache = _prefill(model, params, model.init_kv_cache(NB, BS),
                               tokens, table, chunks)
        return [logp] + [np.asarray(c) for c in jax.tree.leaves(cache)]

    got = run()
    monkeypatch.setattr(
        glm, "split_heads",
        lambda y, heads: y.reshape(*y.shape[:-1], heads, -1))
    want = run()
    assert len(got) == 3 and np.isfinite(want[0]).all()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_engine_serves_it_with_chunks_decode_and_a_prefix_hit():
    """Through EngineCore: chunked prefill, the decode batch with a dispatch
    in flight, prefix reuse — and the counters that say so."""
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    model, params = _model(ONE_INDEX)
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=BS, num_blocks=NB,
        prefill_chunk_tokens=32), eos_token_ids=[])
    assert set(core.attention_impls()) == {"decode", "prefill", "prefill_chunk"}
    doc = [int(t) for t in _tokens(64, seed=5)]
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
    # every decode row saw more context than it selected from
    assert m["attn_selected_tokens_total"] == 16 * m["decode_rows_dispatched_total"]
    assert m["attn_context_tokens_total"] > 4 * m["attn_selected_tokens_total"]
    # greedy tokens are the reference's argmax, teacher-forced
    seq = np.asarray(doc + [9, 8, 7] + got["again"])
    want = _want(ONE_INDEX, params, seq, np.arange(66, 66 + 6))
    assert (want.argmax(-1) == np.asarray(got["again"])).mean() >= 5 / 6


def test_block_movers_are_refused_at_start_up():
    from dynamo_tpu.engine import EngineConfig, EngineCore

    model, params = _model(ONE_INDEX)
    for bad in (dict(num_host_blocks=8), dict(cache_dtype="int8"),
                dict(spec_tokens=2)):
        with pytest.raises(ValueError, match="layout the block movers do not know"):
            EngineCore(model, params, EngineConfig(
                max_batch_size=2, max_model_len=64, block_size=BS,
                num_blocks=16, **bad), eos_token_ids=[])
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=BS, num_blocks=16),
        eos_token_ids=[])
    for move in (lambda: core.gather_blocks_np([1]),
                 lambda: core.gather_blocks_device([1]),
                 lambda: core.scatter_external([1], np.zeros(1))):
        with pytest.raises(NotImplementedError, match="layout the block movers do not know"):
            move()
    assert core.kv_bytes_per_block() == BS * (3 * 128 * 4 + 1 * 16 * 4)


# ------------------------------------------------------ (b) the shares add --
def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts that the four shares of 2 experts give, the shared expert
    counted once, are the layer with all 8 experts resident."""
    whole_cfg = dict(TINY, n_routed_experts=8,
                     expert_parallel={"router_experts": 8, "first_expert": 0})
    whole, wp = _model(whole_cfg)
    g = wp["groups"]["sparse_shared"]
    lp = jax.tree.map(lambda a: a[1], {
        k: v for k, v in g.items() if k not in ("w_gate", "w_up", "w_down")})
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, 64), jnp.float32)
    want = np.asarray(whole._mlp(g, lp, 1, x, dense=False)[0])
    want_ref = np.asarray(ref.experts(
        x[0], {**lp, **{k: g[k][1] for k in ("w_gate", "w_up", "w_down")}},
        whole_cfg))
    np.testing.assert_allclose(want[0], want_ref, atol=2e-4)

    shared = np.asarray(ref.ffn(x[0], lp["shared_gate"], lp["shared_up"],
                                lp["shared_down"]))
    total = np.zeros_like(want[0])
    for first in (0, 2, 4, 6):
        cfg = dict(TINY, expert_parallel={"router_experts": 8,
                                          "first_expert": first})
        part, _ = _model(cfg)
        gs = {**g, **{k: g[k][:, first:first + 2]
                      for k in ("w_gate", "w_up", "w_down")}}
        total += np.asarray(part._mlp(gs, lp, 1, x, dense=False)[0])[0] - shared
    np.testing.assert_allclose(total + shared, want[0], atol=2e-4)
    assert np.abs(total).max() > 10 * 2e-4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_seeded_correction_bias_steers_choices_and_keeps_the_load_even(seed):
    """At the published router (256 sigmoid scores, the 8 largest) the seeded
    e_score_correction_bias must change some choices, or program and
    reference would agree without it, and must not gather every token on a
    few experts: a bias of 0.1 gave the busiest expert 10 times its share and
    the 16 held here 0.4 to 2 times theirs by the seed, so a decode step's
    time followed the seed (PERF.md, PR 37)."""
    from dynamo_tpu.models.deepseek import moe_route
    cfg = GlmDsaConfig.from_hf_config(dict(
        TINY, num_experts_per_tok=8, n_routed_experts=16,
        expert_parallel={"router_experts": 256, "first_expert": 0}),
        dtype="float32")
    kr, kb, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
    dm, tokens = 256, 2048
    router = jax.random.normal(kr, (dm, 256), jnp.float32) / dm ** 0.5
    bias = glm.ROUTER_BIAS_STD * jax.random.normal(kb, (256,), jnp.float32)
    x = jax.random.normal(kx, (tokens, dm), jnp.float32)
    _, with_bias = moe_route(cfg, router, x, bias)
    _, without = moe_route(cfg, router, x, None)
    with_bias, without = np.asarray(with_bias), np.asarray(without)
    changed = np.mean([len(set(a) - set(b)) for a, b in zip(with_bias, without)]) / 8
    assert 0.02 < changed < 0.25, changed
    load = np.bincount(with_bias.ravel(), minlength=256)
    assert load.max() < 3 * load.mean(), load.max() / load.mean()
    held = load[:16].sum() / (load.sum() * 16 / 256)
    assert 0.7 < held < 1.3, held


# ----------------------------------------------------------- (c) IndexShare --
def test_shared_layers_have_no_indexer_and_follow_the_full_layer():
    model, params = _model(TINY)
    for kind, group in params["groups"].items():
        has = any(k.startswith("idx_") for k in group)
        assert has == kind.endswith("_full"), kind
    assert model.init_kv_cache(NB, BS)["index_k"].shape[0] == 2
    assert [(r.kind, r.count) for r in model.runs] == [
        ("dense_full", 1), ("sparse_full", 1), ("sparse_shared", 3)]

    toks = _tokens(64, seed=6)

    def run(p):
        out, _ = _prefill(model, p, model.init_kv_cache(NB, BS), toks,
                          _table(1, 64), [(0, 64)])
        return out

    base = run(params)
    # other indexer weights in the last full layer: what it selects changes,
    # and so does what the shared layers after it attend to
    other = jax.tree.map(lambda a: a, params)
    g = dict(other["groups"]["sparse_full"])
    g["idx_wk"] = jnp.flip(g["idx_wk"], axis=-1)
    other["groups"] = {**other["groups"], "sparse_full": g}
    moved = np.abs(run(other) - base).max(axis=-1)
    assert moved[:16].max() < 1e-5        # below index_topk nothing is cut
    assert moved[32:].max() > 1e-2


# ------------------------------------------------- (d) kernel vs XLA gather --
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_pallas_kernel_matches_the_xla_gather(phase):
    from dynamo_tpu.ops.pallas.mla_sparse_attention import (
        KERNEL_NAMES,
        mla_sparse_attention,
    )

    n, h, width, k, layers, blocks = 5, 4, 40, 32, 2, 12
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((layers * blocks * BS, width)),
                       jnp.bfloat16)
    latent = latent_cache.pack_rows(rows).reshape(layers, blocks, BS, 1, -1)
    np.testing.assert_array_equal(
        np.asarray(latent_cache.unpack_rows(latent)[..., :width]).reshape(
            rows.shape), np.asarray(rows))
    q = jnp.asarray(rng.standard_normal((n, h, width)) * 0.3, jnp.bfloat16)
    slots = jnp.asarray(rng.integers(0, blocks * BS, (n, k)), jnp.int32)
    nvalid = jnp.asarray([32, 17, 1, 0, 9], jnp.int32)
    want = latent_cache.sparse_attention_xla(q, latent, 1, slots, nvalid, 0.2)
    q_lo, q_hi = latent_cache.split_query(q)
    o_lo, o_hi = mla_sparse_attention(
        q_lo, q_hi, slots + blocks * BS, nvalid,
        latent.reshape(-1, 1, latent.shape[-1]), sm_scale=0.2, phase=phase,
        rows_per_tile=8, interpret=True)
    got = jnp.concatenate([o_lo, o_hi], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)
    assert np.abs(np.asarray(got)[3]).max() == 0
    assert KERNEL_NAMES == {"decode": "mla_sparse_decode",
                            "prefill": "mla_sparse_prefill"}


def test_kth_largest_is_exact():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((6, 200)),
                    jnp.float32)
    x = x.at[0, :50].set(-jnp.inf).at[1, 3].set(0.0).at[1, 4].set(-0.0)
    for k in (1, 16, 150, 200):
        np.testing.assert_array_equal(
            np.asarray(glm.kth_largest(x, k))[:, 0],
            np.sort(np.asarray(x), axis=-1)[:, -k])


# (context, k): the first four at a context that is no multiple of 128; then k
# that is none either, the served shape's proportions (2,048 of the 36,864
# positions of a full block table), and a context of less than one lane group
@pytest.mark.parametrize("c,k", [
    (300, 1), (300, 16), (300, 130), (300, 300), (1000, 200), (4133, 2048),
    (36864, 2048), (100, 100)])
def test_selection_without_a_sort_is_top_k(c, k):
    """``select_mask`` + ``selected_slots`` pick what ``lax.top_k`` picks —
    ties to the earlier position, every seen position where fewer than k
    are seen — and list them in ascending order (with each position as its
    own slot, the slots are the positions once more)."""
    rng = np.random.default_rng(k)
    rows = 7
    # + 0.0: no -0.0, which the radix select orders below +0.0 (its note)
    scores = (np.round(rng.standard_normal((rows, c)), 1) + 0.0).astype(
        np.float32)
    seen = np.ones((rows, c), bool)
    seen[1, 40:] = False                        # fewer than k seen
    seen[2] = False                             # nothing seen
    seen[3, ::2] = False
    scores[4] = 0.5                             # all tied
    scores[5, :c // 2] = 0.5                    # tied at the k-th score
    seen[6] = False                             # all in one lane group
    seen[6, 128 * (c // 256):128 * (c // 256) + 100] = True
    scores = np.where(seen, scores, -np.inf)
    sel = glm.select_mask(jnp.asarray(scores), jnp.asarray(seen), k)
    at = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (rows, c))
    pos, slots, count = (a[:, 0] for a in glm.selected_slots(
        sel[:, None, :], k, at, c))
    _, want = jax.lax.top_k(jnp.asarray(scores), k)
    for i in range(rows):
        n = min(k, int(seen[i].sum()))
        expect = sorted(np.asarray(want[i])[:n].tolist())
        assert int(count[i]) == n
        assert np.asarray(pos[i])[:n].tolist() == expect, i
        assert np.asarray(slots[i])[:n].tolist() == expect, i
        assert not np.asarray(pos[i])[n:].any()
        assert sorted(np.flatnonzero(np.asarray(sel[i])).tolist()) == expect


# (rows, queries a row, context, k, block size, blocks of the pool): the
# decode program's proportions; a question's queries, more than one tile of
# them, over one row's table; a context that is no multiple of 128 and a k
# that is none; pools whose slots take three bytes, two to the last bit, one
@pytest.mark.parametrize("b,s,c,k,bs,nb", [
    (4, 1, 36864, 2048, 32, 14400), (1, 96, 4096, 2048, 32, 14400),
    (2, 3, 300, 130, 4, 96), (3, 1, 1000, 200, 8, 70000),
    (2, 1, 1000, 200, 8, 8192), (2, 2, 96, 96, 8, 32)])
def test_slot_list_is_the_block_table_lookup(b, s, c, k, bs, nb):
    """``selected_slots`` hands attention, for every query, the flat cache
    slot ``block_tables[row, pos // bs] * bs + pos % bs`` of each selected
    position in ascending order of position — every integer exact, block
    ids up to the pool's last — and past the query's count the slot of
    position 0, as a lookup of the padding would give."""
    rng = np.random.default_rng(c + k)
    sel = np.zeros((b, s, c), bool)
    for i in range(b):
        for j in range(s):
            n = int(rng.integers(1, c + 1))
            sel[i, j, rng.choice(n, size=min(k, n), replace=False)] = True
    sel[0, 0] = False                           # nothing selected
    sel[-1, -1] = False
    sel[-1, -1, -k:] = True                     # the last k positions
    tables = np.stack([rng.permutation(nb)[:c // bs] for _ in range(b)]
                      ).astype(np.int32)
    tables[0, :2] = nb - 1, 0                   # the pool's last block, and 0
    slot_of = (tables[:, :, None] * bs + np.arange(bs)).reshape(b, c)
    pos, slots, count = jax.jit(
        glm.selected_slots, static_argnums=(1, 3))(
            jnp.asarray(sel), k, jnp.asarray(slot_of), nb * bs)
    assert slots.dtype == jnp.int32 and slots.shape == (b, s, k)
    for i in range(b):
        for j in range(s):
            at = np.flatnonzero(sel[i, j])
            want = np.full(k, tables[i, 0] * bs)
            want[:len(at)] = tables[i, at // bs] * bs + at % bs
            assert int(count[i, j]) == len(at)
            np.testing.assert_array_equal(np.asarray(slots[i, j]), want)
            np.testing.assert_array_equal(np.asarray(pos[i, j])[:len(at)], at)
            assert not np.asarray(pos[i, j])[len(at):].any()


# ------------------------------------------------------ (e) from_hf_config --
def test_from_hf_config_accepts_the_published_keys_and_refuses_the_rest():
    published = json.loads(
        (ROOT / "cellbench/configs/glm-5.2-ep16.json").read_text())
    cfg = GlmDsaConfig.from_hf_config(published)
    assert (cfg.router_experts, cfg.n_routed_experts, cfg.expert_first) == (
        256, 16, 0)
    assert (cfg.index_topk, cfg.kv_lora_rank, cfg.head_dim) == (2048, 512, 576)
    assert cfg.rope_theta == 8_000_000 and cfg.full_layers == 2
    for bad in (
        {"n_group": 8, "topk_method": "greedy"}, {"q_lora_rank": None},
        {"scoring_func": "tanh"}, {"topk_method": "x"},
        {"attention_bias": True}, {"hidden_act": "gelu"},
        {"rope_interleave": False}, {"index_topk_pattern": [1]},
        {"rope_parameters": {"rope_type": "yarn", "rope_theta": 1.0}},
        {"indexer_types": ["full", "sliding", "shared", "shared", "shared"]},
    ):
        with pytest.raises(NotImplementedError):
            GlmDsaConfig.from_hf_config({**published, **bad})
    grouped = GlmDsaConfig.from_hf_config(
        {**published, "n_group": 8, "topk_group": 4})
    assert (grouped.n_group, grouped.topk_group) == (8, 4)
    for bad in ({"indexer_types": ["full"]},
                {"indexer_types": ["shared"] + ["full"] * 4},
                {"n_group": 8, "topk_group": 9}, {"n_group": 7},
                {"expert_parallel": {"router_experts": 256,
                                     "first_expert": 250}}):
        with pytest.raises(ValueError):
            GlmDsaConfig.from_hf_config({**published, **bad})


# -------------------------------------------- (f) the file's per-layer lists --
def test_configuration_lists_are_the_published_slice():
    published = None
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.is_file():
        for line in catalog.read_text().splitlines():
            row = json.loads(line)
            if row["name"] == "GLM-5.2":
                published = row["config"]
    cfg = json.loads(
        (ROOT / "cellbench/configs/glm-5.2-ep16.json").read_text())
    n = cfg["num_hidden_layers"]
    assert len(cfg["indexer_types"]) == len(cfg["mlp_layer_types"]) == n == 5
    assert cfg["mlp_layer_types"].count("dense") == cfg["first_k_dense_replace"]
    if published is None:
        pytest.skip("no catalog here: lengths checked, not the slice")
    kept = [0, 6, 7, 8, 9]      # one leading dense layer + one whole period
    assert cfg["indexer_types"] == [published["indexer_types"][i] for i in kept]
    assert cfg["mlp_layer_types"] == [published["mlp_layer_types"][i]
                                      for i in kept]
    cut = set(cfg["reduced"])
    for key, value in published.items():
        if key not in cut:
            assert cfg[key] == value, key
