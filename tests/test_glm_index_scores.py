"""The indexer's scores of a decode step, from the keys where they lie
(ops/pallas/dsa_index_scores.py, interpret mode) against ``index_scores`` over
the gathered keys (models/glm_dsa.py, the XLA form and the kernel's oracle):
rows alone and in groups, lengths that end anywhere, the selection that
follows, the count of the keys fetched, and a decode step of the model through
either form."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.models.glm_dsa as glm
from dynamo_tpu.ops import latent_cache
from dynamo_tpu.ops.pallas import dsa_index_scores as dsa
from dynamo_tpu.ops.pallas import registry
from dynamo_tpu.ops.pallas.mla_dense_attention import decode_groups
from test_glm_dsa import ONE_INDEX, _model, _tokens

H, D, KBS, C = 4, 128, 32, 8             # eight blocks of 32 keys a chunk
M = 24                                   # three chunks a table


def _inputs(tables, lens, seed=0, n=64):
    """(q, w, keys, tables, lens) for rows of ``tables`` [B, M] over a pool
    of ``n`` blocks; what no row owns is NaN, so a fetch too many shows."""
    rng = np.random.default_rng(seed)
    tables, lens = np.asarray(tables, np.int32), np.asarray(lens, np.int32)
    b = len(lens)
    keys = rng.standard_normal((n, KBS, D)).astype(np.float32)
    owned = np.zeros(n, bool)
    for r in range(b):
        owned[tables[r, :-(-lens[r] // KBS)]] = True
    keys[~owned] = np.nan
    return (jnp.asarray(rng.standard_normal((b, H, D)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((b, H)), jnp.bfloat16),
            jnp.asarray(keys, jnp.bfloat16), jnp.asarray(tables),
            jnp.asarray(lens))


def _seen(lens, m=M):
    return np.arange(m * KBS)[None, :] < np.asarray(lens)[:, None]


def _kernel(q, w, keys, tables, lens, g=8, groups=None):
    out = dsa.dsa_index_scores(q, w, keys, tables, lens, groups,
                               blocks_per_chunk=C, group_rows=g,
                               interpret=True)
    return np.asarray(out)


def _oracle(q, w, keys, tables, lens):
    """``index_scores`` over every row's whole table, NaN keys as zeros."""
    b, m = tables.shape
    gathered = jnp.nan_to_num(keys.astype(jnp.float32))[tables].reshape(
        b, m * KBS, D).astype(keys.dtype)
    return np.asarray(glm.index_scores(q[:, None], w[:, None], gathered))[:, 0]


def _table(doc, own, m=M):
    row = np.zeros(m, np.int32)
    ids = list(doc) + list(own)
    row[:len(ids)] = ids
    return row


DOC = list(range(1, 17))                 # sixteen blocks: two whole chunks
CASES = {
    # a row alone, a length that ends inside a block, an empty slot
    "alone": ([_table(DOC, [40]), _table([], []), _table([50, 51], [])],
              [530, 0, 45]),
    "group-of-2": ([_table(DOC, [40]), _table(DOC, [41, 42, 43])], [520, 600]),
    # lengths that differ by less than a chunk, and by more (the second
    # member ends inside the document's second chunk: one chunk is shared)
    "group-of-3": ([_table(DOC, [40]), _table(DOC, []),
                    _table(DOC, [42, 43, 44, 45])], [513, 400, 640]),
    "group-of-8": ([_table(DOC, [40 + i]) for i in range(8)],
                   [513 + 3 * i for i in range(8)]),
    # ten rows on one document: a group of eight and a group of two
    "more-than-a-group": ([_table(DOC, [40 + i]) for i in range(10)],
                          [544 - i for i in range(10)]),
    # rows that share no block, one of them the whole table
    "no-block-shared": ([_table(range(1, 25), []), _table(range(25, 39), []),
                         _table([60], [])], [768, 448, 1]),
    # the same first blocks, less than a chunk of them
    "less-than-a-chunk-shared": ([_table(range(1, 8), [40, 41]),
                                  _table(range(1, 8), [42, 43])], [280, 270]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scores_are_the_xla_forms_at_every_position_a_row_sees(case):
    tables, lens = CASES[case]
    args = _inputs(tables, lens, seed=len(case))
    got, want = _kernel(*args), _oracle(*args)
    seen = _seen(lens)
    assert got.shape == want.shape == (len(lens), M * KBS)
    assert np.isfinite(got[seen]).all()
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5)
    assert np.abs(want[seen]).max() > 0.1


@pytest.mark.parametrize("case", ["group-of-2", "group-of-3", "group-of-8",
                                  "more-than-a-group"])
def test_a_rows_scores_do_not_depend_on_who_shares_its_group(case):
    """A score is made of its own key and its row's query alone, whatever
    is stacked beside it: on the chip the same bits at every group cap (the
    matrix unit's result for a row does not know the other rows:
    ``benchmarks/probe_kernels.py indexer`` reads ``same_bits_as_alone``);
    the CPU's matrix product blocks its sums by the number of rows, so here
    they are held to f32 rounding, and the selection to equality."""
    tables, lens = CASES[case]
    args = _inputs(tables, lens, seed=1)
    seen = _seen(lens)
    alone = _kernel(*args, g=1)
    picked = glm.select_mask(jnp.where(seen, alone, -jnp.inf),
                             jnp.asarray(seen), 48)
    for g in (2, 4, 8):
        got = _kernel(*args, g=g)
        np.testing.assert_allclose(got[seen], alone[seen], rtol=1e-5,
                                   atol=1e-5)
        assert (np.asarray(glm.select_mask(
            jnp.where(seen, got, -jnp.inf), jnp.asarray(seen), 48))
            == np.asarray(picked)).all(), g
    bt, ln = np.asarray(tables, np.int32), np.asarray(lens, np.int32)
    assert decode_groups(np, bt, ln, KBS, C, 8)[:, 0].max() > 1
    assert decode_groups(np, bt, ln, KBS, C, 1)[:, 0].max() == 1


def test_the_step_s_groups_are_the_kernel_s_own():
    tables, lens = CASES["group-of-3"]
    args = _inputs(tables, lens)
    groups = decode_groups(jnp, args[3], args[4], KBS, C, 8)
    seen = _seen(lens)
    assert (_kernel(*args, groups=groups)[seen] == _kernel(*args)[seen]).all()


def test_the_selection_is_the_xla_forms_with_ties_at_the_boundary():
    """Queries, keys and weights of small whole numbers: every product and
    sum is exact whatever its order, so both forms give the same bits, many
    positions score alike, the k-th score is one of a tie, and
    ``select_mask`` keeps the earliest of the tied — the same positions from
    either form's scores."""
    tables, lens = CASES["group-of-3"]
    _, _, keys, bt, ln = _inputs(tables, lens, seed=5)
    rng = np.random.default_rng(5)
    whole = lambda lo, hi, shape: jnp.asarray(
        rng.integers(lo, hi + 1, shape), jnp.bfloat16)
    q, w = whole(-2, 2, (3, H, D)), whole(-2, 2, (3, H))
    keys = jnp.where(jnp.isnan(keys), keys, whole(-1, 1, keys.shape))
    seen = _seen(lens)
    got, want = _kernel(q, w, keys, bt, ln), _oracle(q, w, keys, bt, ln)
    assert (got[seen] == want[seen]).all()
    picks = [np.asarray(glm.select_mask(
        jnp.where(seen, scores, -jnp.inf), jnp.asarray(seen), 64))
        for scores in (got, want)]
    cut = []
    for row in range(3):
        kth = np.sort(want[row][seen[row]])[-64]
        tied = (want[row] == kth) & seen[row]
        assert tied.sum() > 1
        cut.append(not picks[1][row][tied].all())
    assert any(cut)          # a tie at the boundary of which not all are kept
    assert (picks[0] == picks[1]).all()
    assert picks[0].sum(axis=1).tolist() == [64, 64, 64]


def test_keys_read_is_whole_blocks_with_a_group_s_shared_ones_once():
    tables, lens = CASES["group-of-3"]
    bt, ln = np.asarray(tables, np.int32), np.asarray(lens, np.int32)
    owned = -(-ln // KBS)                        # 17, 13, 20 blocks
    # lengths 513 / 400 / 640: the members share the document's first chunk
    # (the second member ends inside the second), fetched once for three
    assert dsa.index_keys_read(bt, ln, KBS, C, 8) == (
        owned.sum() - 2 * C) * KBS
    assert dsa.index_keys_read(bt, ln, KBS, C, 1) == owned.sum() * KBS
    # an empty slot reads nothing, and a length past the table is cut to it
    assert dsa.index_keys_read(bt[:1], np.array([0], np.int32), KBS, C, 8) == 0
    assert dsa.index_keys_read(
        bt[:1], np.array([M * KBS + 99], np.int32), KBS, C, 8) == M * KBS
    # and the model offers it where it has an indexer (EngineCore's count)
    model, _ = _model(ONE_INDEX)
    assert model.index_keys_read is dsa.index_keys_read
    assert model.decode_rows_fetched is None


def test_the_kernel_is_taken_where_a_step_s_scores_fit():
    # the cell: 32 rows over 36,864 positions, chunks of 1,024 keys
    assert dsa.fits(32, 1152, 32, 32, 128)
    assert registry.dsa_index_vmem_bytes(32, 36864, 32, 128, 1024, 8) \
        < registry.DSA_INDEX_VMEM_BYTES < registry.VMEM_BUDGET_BYTES
    assert not dsa.fits(64, 4096, 32, 32, 128)      # 64 x 131,072 scores
    assert not dsa.fits(32, 1152, 32, 32, 64)       # half a lane group a key
    assert not dsa.fits(4, 14, 8, 8, 128)           # 112 keys a chunk
    assert not dsa.fits(4, 4, 32, 8, 128)           # one lane group a chunk
    assert dsa.fits(4, 24, KBS, H, D)               # this file's geometry


@pytest.fixture
def kernels_in_interpret_mode(monkeypatch):
    """The latent cache as on the TPU — rows written by the DMA mover, a
    decode step's index scores by ``dsa_index_scores`` — interpreted."""
    from dynamo_tpu.ops.pallas import latent_cache_dma

    monkeypatch.setattr(latent_cache, "kernels_on", lambda: True)
    for mod, name in ((latent_cache_dma, "write_rows"),
                      (latent_cache_dma, "gather_blocks"),
                      (dsa, "dsa_index_scores")):
        monkeypatch.setattr(mod, name, functools.partial(
            getattr(mod, name), interpret=True))


def _decode_step(model, params, cache, rows, width):
    """One decode step (``probe=True``) of a batch of four: ``rows`` of
    (tokens cached, table, next token); the last slots idle."""
    bt = np.zeros((4, width), np.int32)
    tok, pos = np.zeros((4, 1), np.int32), np.zeros((4, 1), np.int32)
    slot, lens = np.full((4, 1), -1, np.int32), np.zeros(4, np.int32)
    for i, (n, table, nxt) in enumerate(rows):
        bt[i, :len(table)] = table
        tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
        slot[i, 0] = table[n // KBS] * KBS + n % KBS
    return model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray(lens), jnp.asarray(slot), probe=True)


def test_a_decode_step_selects_the_same_through_either_form(
        kernels_in_interpret_mode, monkeypatch):
    """Two rows behind one document of 384 tokens (a whole chunk of twelve
    blocks of a table of sixteen) and a row alone, decoded once with the
    kernel and once with the gathered keys: the same selection, the same
    hidden state to rounding."""
    cfg = dict(ONE_INDEX, index_head_dim=128)
    model, params = _model(cfg)
    width, doc = 16, _tokens(384, seed=11)
    cache = model.init_kv_cache(40, KBS)
    shared = np.arange(1, 13, dtype=np.int32)

    def prefill(cache, tokens, table, start):
        bt = np.zeros((1, width), np.int32)
        bt[0, :len(table)] = table
        pos = np.arange(start, len(tokens), dtype=np.int32)[None]
        slots = bt[0, pos // KBS] * KBS + pos % KBS
        _, cache = model.forward(
            params, jnp.asarray(tokens[None, start:], jnp.int32),
            jnp.asarray(pos), cache, jnp.asarray(bt),
            jnp.asarray([len(tokens)], jnp.int32), jnp.asarray(slots),
            prefix_blocks=start // KBS)
        return cache

    cache = prefill(cache, doc, shared, 0)
    asks = [np.concatenate([doc, _tokens(n, seed=s)])
            for n, s in ((7, 12), (40, 13))]
    tables = [np.concatenate([shared, own]).astype(np.int32)
              for own in ([20], [21, 22])]
    for tokens, table in zip(asks, tables):
        cache = prefill(cache, tokens, table, 384)
    lone = _tokens(50, seed=14)
    cache = prefill(cache, lone, np.array([30, 31], np.int32), 0)
    rows = [(len(asks[0]), tables[0], 5), (len(asks[1]), tables[1], 9),
            (50, np.array([30, 31], np.int32), 3)]

    calls = []
    real = dsa.dsa_index_scores
    monkeypatch.setattr(dsa, "dsa_index_scores",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    h_kernel, _, picked_kernel = _decode_step(model, params, cache, rows, width)
    assert len(calls) == 1                      # the one ``full`` layer
    monkeypatch.setattr(latent_cache, "index_decode_groups",
                        lambda *a, **kw: None)
    h_xla, _, picked_xla = _decode_step(model, params, cache, rows, width)
    assert len(calls) == 1
    (pos_k, val_k, n_k), (pos_x, val_x, n_x) = picked_kernel[0], picked_xla[0]
    assert np.asarray(n_k).tolist() == np.asarray(n_x).tolist() == [16, 16, 16, 0]
    assert (np.asarray(pos_k) == np.asarray(pos_x)).all()
    np.testing.assert_allclose(np.asarray(val_k)[:3], np.asarray(val_x)[:3],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_kernel)[:3], np.asarray(h_xla)[:3],
                               atol=1e-4)
    assert np.isfinite(np.asarray(h_kernel)).all()


def test_engine_counts_the_index_keys_a_decode_step_reads_for_sharers():
    """Three requests ask one cached document of 256 tokens together (32
    blocks of 8: one chunk of the kernel's 32 blocks): a decode dispatch of n
    of them reads the document's keys once — by hand, the rows' whole blocks
    less (n - 1) x 256 — over the 4 x 40 x 8 positions of its block tables;
    alone, a row reads every block it owns.  A model without an indexer
    counts neither."""
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    bs = 8
    model, params = _model(ONE_INDEX)
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=320, block_size=bs, num_blocks=160,
        prefill_chunk_tokens=64), eos_token_ids=[])
    doc = [int(t) for t in _tokens(256, seed=3)]
    dispatched = []
    run = core._run_multi_decode_step

    def spy(tokens, positions, bt, seq_lens, *a, **kw):
        dispatched.append(seq_lens.copy())
        return run(tokens, positions, bt, seq_lens, *a, **kw)

    core._run_multi_decode_step = spy

    def ask(*questions):
        for i, question in enumerate(questions):
            core.submit(EngineRequest(
                request_id=f"r{len(dispatched)}-{i}", prompt=doc + question,
                sampling=SamplingOptions(temperature=0.0),
                stops=StopConditions(max_tokens=5, ignore_eos=True),
                emit=lambda o: None))
        while core.step():
            pass

    blocks = lambda lens: int((-(-lens // bs) * bs).sum())
    ask([3, 4, 5])                                  # alone: nothing shared
    m = core.metrics()
    assert m["index_keys_read_total"] == sum(map(blocks, dispatched)) > 0
    ask([6, 7], [8, 9, 10, 11], [12])
    m = core.metrics()
    live = [int((lens > 0).sum()) for lens in dispatched]
    assert max(live) == 3
    assert m["index_keys_table_total"] == len(dispatched) * 4 * 40 * bs
    assert m["index_keys_read_total"] == sum(
        blocks(lens) - (n - 1) * 256 for lens, n in zip(dispatched, live))
    assert m["index_keys_read_total"] < 0.25 * m["index_keys_table_total"]
    assert m["attn_fetched_tokens_total"] == 0      # the dense kernel's count

    from test_mistral4_served import build

    dense, dense_params = build()
    other = EngineCore(dense, dense_params, EngineConfig(
        max_batch_size=4, max_model_len=64, block_size=bs, num_blocks=40,
        prefill_chunk_tokens=32), eos_token_ids=[])
    other.submit(EngineRequest(
        request_id="dense", prompt=doc[:20],
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=3, ignore_eos=True),
        emit=lambda o: None))
    while other.step():
        pass
    m = other.metrics()
    assert m["decode_dispatches_total"] > 0
    assert m["index_keys_table_total"] == m["index_keys_read_total"] == 0
