"""The two forms of a GLM-5.2 prefill chunk's attention over a selection
(models/glm_dsa.py::attends_masked): the masked kernel with true lengths
(ops/pallas/mla_masked_prefill.py, interpret mode) against the XLA form at a
question's shapes, the masked form against the gather for one selection, the
rule's choices, and the engine's count of the tokens that went masked."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.models.glm_dsa as glm
from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
from dynamo_tpu.ops import latent_cache
from dynamo_tpu.ops.pallas import registry
from test_glm_dsa import (
    BS, NB, ONE_INDEX, TINY, _close, _model, _prefill, _table, _tokens, _want,
)

H, WIDTH, DV, KBS = 2, 96, 64, 32       # two heads, rows of 96 in 128 lanes


@pytest.fixture
def kernels_in_interpret_mode(monkeypatch):
    """``latent_cache.masked_attention`` as on the TPU — the block gather,
    the padding to whole key tiles, the kernel — with both Pallas calls
    interpreted."""
    from dynamo_tpu.ops.pallas import latent_cache_dma, mla_masked_prefill

    monkeypatch.setattr(latent_cache, "kernels_on", lambda: True)
    for mod, name in ((latent_cache_dma, "gather_blocks"),
                      (mla_masked_prefill, "mla_sparse_prefill_masked")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))


def _question(s, live, c, ctx, seed=0, share=0.1):
    """A latent cache, a block table of ``c`` positions of which ``ctx``
    exist, ``s`` query tokens of which the first ``live`` exist and end the
    context, and a causal mask with ``share`` of the visible keys selected;
    the blocks no live key tile reaches hold NaN in the second cache."""
    rng = np.random.default_rng(seed)
    blocks = c // KBS
    rows = rng.standard_normal((blocks + 8, KBS, WIDTH)).astype(np.float32)
    bt = rng.permutation(blocks + 8)[:blocks].astype(np.int32)
    tk = registry.MLA_MASKED_KEYS_PER_TILE
    dead = bt[-(-ctx // tk) * tk // KBS:]       # wholly past the context
    poisoned = rows.copy()
    poisoned[dead] = np.nan
    pack = lambda r: latent_cache.pack_rows(
        jnp.asarray(r, jnp.bfloat16))[None, :, :, None, :]
    mask = np.tril(np.ones((s, c), bool), k=ctx - live)
    mask &= rng.random((s, c)) < share
    mask[live:] = False
    mask[:, ctx:] = False
    q = jnp.asarray(rng.standard_normal((1, s, H, WIDTH)) * 0.3, jnp.bfloat16)
    return (q, pack(rows), pack(poisoned), jnp.asarray(bt)[None],
            mask, jnp.asarray([live], jnp.int32), jnp.asarray([ctx], jnp.int32))


@pytest.mark.parametrize("s,live,c,ctx", [
    (64, 50, 16512, 12000),       # a context that ends inside a key tile
    (128, 100, 32896, 30000),     # 257 x 128: the tile was 128 keys here
    (256, 160, 33024, 24700),     # six dead query tiles, sixteen dead key tiles
    (256, 256, 33024, 33024),     # nothing dead but the padding to 33,280
    (128, 1, 16512, 513),         # one token, one key past a tile's end
    (64, 64, 16512, 64),          # a chunk with no prefix in a long table
], ids=["s64-c16512", "s128-c32896", "s256-c33024", "s256-whole",
        "one-token", "no-prefix"])
def test_masked_kernel_with_true_lengths_matches_the_xla_form(
        kernels_in_interpret_mode, s, live, c, ctx):
    q, latent, poisoned, bt, mask, lv, lens = _question(s, live, c, ctx)
    mask[:, ctx - live] = mask.any(axis=1) | (np.arange(s) < live)
    empty = 3 if live > 3 else live             # a query with nothing selected
    mask[empty:empty + 1] = False
    mask = jnp.asarray(mask)[None]
    want = latent_cache.dense_masked_attention(
        q, latent_cache.unpack_rows(
            latent[0, bt[0]].reshape(1, c, -1)), mask, 0.2)[..., :128]
    got = latent_cache.masked_attention(
        q, poisoned, jnp.int32(0), bt, mask, 0.2, DV, lv, lens)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (1, s, H, 128) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2)
    assert np.abs(got[0, live:]).max(initial=0) == 0      # dead tokens
    assert np.abs(got[0, empty:empty + 1]).max(initial=0) == 0
    assert np.abs(want[0, :live]).max() > 0.1


def test_a_dead_step_names_the_blocks_of_the_last_live_one(
        kernels_in_interpret_mode):
    """Past the live tokens and past the context a grid step's blocks are
    the ones in VMEM already, so nothing is fetched for it; the cost
    function counts the live tiles."""
    q, latent, _, bt, mask, lv, lens = _question(64, 20, 2048, 700)
    records = []
    with registry.capture_pallas_calls(records):
        latent_cache.masked_attention(
            q, latent, jnp.int32(0), bt, jnp.asarray(mask)[None], 0.2, DV,
            lv, lens)
    call = [r for r in records if tuple(r["grid"]) == (4, 4)]
    assert len(call) == 1
    specs = call[0]["in_specs"]
    at = np.asarray([20, 700], np.int32)
    seen = {(i, j): tuple(int(x) for spec in specs
                          for x in spec.index_map(i, j, at))
            for i in range(4) for j in range(4)}
    # (q tile, 0, key tile, 0, q tile, key tile): two live tiles each way
    assert seen[(0, 0)] == (0, 0, 0, 0, 0, 0)
    assert seen[(1, 1)] == (1, 0, 1, 0, 1, 1)
    assert seen[(1, 3)] == seen[(1, 2)] == seen[(1, 1)]       # past 700
    assert seen[(2, 0)] == seen[(3, 3)] == seen[(1, 1)]       # past 20
    want = registry.mla_masked_cost(64, 2048, H, 128, 128, live=20, ctx=700)
    assert want["flops"] == 2 * (32 * H) * 1024 * (128 + 128)
    assert want["flops"] * 4 == registry.mla_masked_cost(
        64, 2048, H, 128, 128)["flops"]


@pytest.mark.parametrize("live,ctx", [(40, 200), (7, 64), (48, 256)])
def test_masked_and_gather_kernels_agree_on_one_selection(live, ctx):
    """One selection, as a mask and as row lists, through both kernels."""
    from dynamo_tpu.ops.pallas.mla_masked_prefill import (
        mla_sparse_prefill_masked,
    )
    from dynamo_tpu.ops.pallas.mla_sparse_attention import mla_sparse_attention

    s, c, k = 48, 256, 32
    q, latent, _, bt, mask, lv, lens = _question(s, live, c, ctx, share=0.12)
    mask[:, 0] = mask.any(axis=1)               # bound a list to k rows
    mask &= np.cumsum(mask, axis=1) <= k
    slot_of = (np.asarray(bt[0])[:, None] * KBS + np.arange(KBS)).reshape(c)
    slots = np.zeros((s, k), np.int32)
    nvalid = mask.sum(axis=1).astype(np.int32)
    for t in range(s):
        slots[t, :nvalid[t]] = slot_of[mask[t]]
    q_lo, q_hi = latent_cache.split_query(q[0])
    o_lo, o_hi = mla_sparse_attention(
        q_lo, q_hi, jnp.asarray(slots), jnp.asarray(nvalid),
        latent.reshape(-1, 1, latent.shape[-1]), sm_scale=0.2,
        phase="prefill", rows_per_tile=8, interpret=True)
    gathered = np.asarray(o_lo)                 # elements 0..127 of a row
    context = latent_cache.unpack_rows(latent[0, bt[0]].reshape(c, -1))
    masked = mla_sparse_prefill_masked(
        latent_cache._pad_to(q[0], 128).reshape(s * H, 128),
        context[:, :128], jnp.where(jnp.asarray(mask), 0.0, -1e30),
        jnp.asarray([live, ctx]), heads=H, dv=128, sm_scale=0.2,
        tokens_per_tile=8, keys_per_tile=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(masked).reshape(s, H, 128), gathered, atol=2e-2)
    assert np.abs(gathered[:live]).max() > 0.1


@pytest.mark.parametrize("chunks", [
    [(0, 80)], [(0, 32), (32, 64), (64, 80)], [(0, 64), (64, 77), (77, 80)]],
    ids=["whole", "chunked", "question-after-a-prefix"])
def test_model_gives_the_same_output_in_both_forms(chunks, monkeypatch):
    """The same chunks through ``forward`` with the rule held to each form:
    the selection is one ``select_mask`` in both, so the log-probabilities
    agree to rounding and both hold to the reference."""
    model, params = _model(ONE_INDEX)
    toks = _tokens(80, seed=6)
    got = {}
    for form in (True, False):
        monkeypatch.setattr(glm, "masked_prefill_is_cheaper",
                            lambda context, topk, form=form: form)
        got[form], _ = _prefill(model, params, model.init_kv_cache(NB, BS),
                                toks, _table(1, 80), chunks)
    # both round the rows and the weights to bf16, in different orders
    _close(got[True], got[False], every=0.04)
    _close(got[True], _want(ONE_INDEX, params, toks, np.arange(80)),
           every=0.06)


def _glm_shaped():
    """GLM-5.2's ``index_topk`` on the toy's widths: the rule reads nothing
    else of a configuration."""
    return GlmDsaModel(GlmDsaConfig.from_hf_config(
        dict(TINY, index_topk=2048), dtype="float32"))


@pytest.mark.parametrize("b,s,table,pb,probe,want", [
    (32, 1, 1088, None, False, False),          # a decode step
    (1, 1, 1088, None, False, False),           # ... of one row
    (2, 256, 1088, 512, False, False),          # a batch of sequences
    (1, 256, 1088, 1024, True, False),          # the long-context check
    (1, 64, 1088, 512, False, True),            # a question at the 16 k bucket
    (1, 256, 1088, 512, False, True),
    (1, 256, 1088, 1024, False, True),          # ... at the 33 k bucket
    (1, 2048, 1088, 0, False, True),            # a document's first chunk
    (1, 2048, 1088, 1024, False, True),         # ... and its last
    (1, 256, 4104, 1024, False, True),          # the table's width is not read
    (1, 256, 4104, 2048, False, False),         # 64 k: the gather is cheaper
    (1, 256, 4104, 4096, False, False),         # 128 k
], ids=["decode", "decode-one-row", "batch", "probe", "question-64-16k",
        "question-256-16k", "question-256-33k", "chunk-first", "chunk-last",
        "wide-table", "context-64k", "context-128k"])
def test_the_rule_chooses_by_the_call_s_static_shape(b, s, table, pb, probe,
                                                     want):
    assert _glm_shaped().attends_masked(b, s, table, 32, pb, probe) is want


def test_the_rule_is_two_measured_constants_and_the_tile():
    model = _glm_shaped()
    tiles = model.masked_up_to() // registry.MLA_MASKED_KEYS_PER_TILE
    a_token = (registry.MLA_MASKED_TILE_NS
               / registry.MLA_MASKED_TOKENS_PER_TILE)
    assert tiles * a_token < 2048 * registry.MLA_SPARSE_ROW_NS \
        <= (tiles + 1) * a_token
    assert 49152 < model.masked_up_to() < 65536
    assert registry.masked_prefill_is_cheaper(33280, 2048)
    assert not registry.masked_prefill_is_cheaper(131072, 2048)
    # few selected rows are few DMAs: a short selection gathers sooner
    assert not registry.masked_prefill_is_cheaper(33280, 256)
    # no indexer, no selection, neither form
    plain = GlmDsaModel(GlmDsaConfig.from_hf_config(
        dict(TINY, indexer_types=["none"] * 5, index_topk=0,
             index_n_heads=0, index_head_dim=0), dtype="float32"))
    assert not plain.attends_masked(1, 256, 1088, 32, 1024)
    impls = model.attention_impls()
    assert "mla_sparse_prefill_masked" in impls["prefill_chunk"][1]
    assert f"{model.masked_up_to():,}" in impls["prefill_chunk"][1]


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "gather"])
def test_engine_counts_the_tokens_whose_chunk_went_masked(masked,
                                                          monkeypatch):
    """The engine counts by the rule ``forward`` traced by: every computed
    prompt token where it says masked, none where it says gather — and the
    form that ran is the one counted."""
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    monkeypatch.setattr(glm, "masked_prefill_is_cheaper",
                        lambda context, topk: masked)
    calls = []
    real = latent_cache.masked_attention
    monkeypatch.setattr(latent_cache, "masked_attention", lambda *a, **kw: (
        calls.append(a[0].shape), real(*a, **kw))[1])
    model, params = _model(ONE_INDEX)
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=BS, num_blocks=NB,
        prefill_chunk_tokens=32), eos_token_ids=[])
    doc = [int(t) for t in _tokens(64, seed=5)]
    for name, question in (("first", [3, 4, 5, 6, 7]), ("again", [9, 8, 7])):
        core.submit(EngineRequest(
            request_id=name, prompt=doc + question,
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=4, ignore_eos=True),
            emit=lambda o: None))
        while core.step():
            pass
    m = core.metrics()
    assert core.prompt_tokens_computed == 69 + 3
    assert m["prefill_masked_tokens_total"] == (72 if masked else 0)
    assert bool(calls) == masked
    assert all(shape[0] == 1 and shape[1] > 1 for shape in calls)
