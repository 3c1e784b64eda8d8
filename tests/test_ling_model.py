"""models/hybrid_linear.py with latent attending layers (``ling_hybrid_mla``)
against the benchmark's plain reference (cellbench/reference/
ling_hybrid_mla.py) by direct calls of ``forward``: prefill in chunks then
decode through the cache, a slot taken again, rows with no real token, and a
cut of the stack read through ``published_layers``."""

import numpy as np

from hybrid_linear_tiny import BS, NB, SLOTS, tokens_of
from ling_tiny import TINY, build, chunk, decode, exact_attention, want

# float32 on both sides and float32 attention: the order of the sums (chunked
# scan against token by token, absorbed against expanded, paged against dense)
EXACT = 2e-4
# ... and with the XLA form of dense latent attention as it is served off the
# TPU: its queries and probabilities rounded to bf16
ROUNDING = 0.02


def fresh_cache(model):
    return model.init_kv_cache(NB, BS, slots=SLOTS)


def test_prefill_in_chunks_is_the_reference(monkeypatch):
    """75 tokens in chunks of 32, 32 and 11 (the last padded to 32: one
    program for the three, each reading the whole table) in slot 2: every
    position's log-probabilities against the reference's full forward — the
    delta-rule state, the convolution's tail and the latent rows of two MLA
    layers all cross both chunk boundaries.  (The engine's own buckets of
    ``prefix_blocks`` are tests/test_ling_served.py's, through
    ``EngineCore``, and the next tests'.)"""
    exact_attention(monkeypatch)
    model, params = build()
    assert [r.kind for r in model.runs] == [
        "linear_dense", "linear", "mla", "linear", "mla"]
    toks = tokens_of(75, 1)
    cache = fresh_cache(model)
    assert sorted(cache) == ["conv", "latent", "moe_counts", "state",
                             "state_pos"]
    assert cache["latent"].shape == (2, NB, BS, 128)       # 32 + 8 -> 128 lanes
    got = []
    for a, b, pad in ((0, 32, None), (32, 64, None), (64, 75, 32)):
        lp, cache = chunk(model, params, cache, toks, a, b, 2, 1, pad,
                          whole_table=True)
        got.append(lp)
    got = np.concatenate(got)
    assert np.abs(got - want(params, toks, np.arange(75))).max() < EXACT
    counts = np.asarray(cache["moe_counts"])
    assert counts[0, 0, 4] == 4 * 75                       # tokens x KDA layers
    assert counts[0, 0, 5] == 1 and counts[0, 0, 6] == 0   # resets, mismatches
    assert counts.shape[-1] == 7
    # the dense layer routes nothing; each expert layer picks top-2 a token
    assert counts[0, 0, 0] == 0 and list(counts[1:, 0, 0]) == [2 * 75] * 5
    assert list(np.asarray(cache["state_pos"])) == [0, 0, 75, 0]


def test_decode_through_the_cache_is_the_reference(monkeypatch):
    """Two sequences prefilled into slots 2 and 0 (32 and 20 tokens), then
    five decode steps over the slot array with both alive and two slots idle:
    each step reads the state its slot holds and every latent row its blocks
    hold, against the reference's full forward over each sequence."""
    exact_attention(monkeypatch)
    model, params = build()
    toks, other = tokens_of(37, 1), tokens_of(25, 2)
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, other, 0, 20, 0, 20, pad_to=32)
    _, cache = chunk(model, params, cache, toks, 0, 32, 2, 1)
    got, got_other = [], []
    for n in range(32, 37):
        lp, cache = decode(model, params, cache, {
            2: (n, 1, toks[n]), 0: (n - 12, 20, other[n - 12])})
        got.append(lp[2])
        got_other.append(lp[0])
    assert np.abs(np.stack(got) - want(
        params, toks, np.arange(32, 37))).max() < EXACT
    assert np.abs(np.stack(got_other) - want(
        params, other, np.arange(20, 25))).max() < EXACT
    counts = np.asarray(cache["moe_counts"])
    run = 20 + 32 + 2 * 5
    assert counts[0, 0, 4] == 4 * run
    assert counts[0, 0, 5] == 2 and counts[0, 0, 6] == 0
    assert list(np.asarray(cache["state_pos"])) == [25, 0, 37, 0]


def test_served_attention_stays_within_its_rounding():
    """The same run through the XLA form as it is: bf16 queries and
    probabilities in two of six layers."""
    model, params = build()
    toks = tokens_of(48, 7)
    cache = fresh_cache(model)
    lp1, cache = chunk(model, params, cache, toks, 0, 32, 1, 3)
    lp2, cache = chunk(model, params, cache, toks, 32, 47, 1, 3, pad_to=16)
    lp3, cache = decode(model, params, cache, {1: (47, 3, toks[47])})
    got = np.concatenate([lp1, lp2, lp3[1:2]])
    delta = np.abs(got - want(params, toks, np.arange(48)))
    assert EXACT < delta.max() < ROUNDING


def test_a_slot_taken_again_starts_from_zero(monkeypatch):
    """A second sequence in the slot and the blocks the first one used:
    position 0 resets state and tail, and the first one's latent rows past
    the second's length are never read."""
    exact_attention(monkeypatch)
    model, params = build()
    first, second = tokens_of(50, 5), tokens_of(33, 6)
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, first, 0, 32, 1, 4)
    _, cache = chunk(model, params, cache, first, 32, 50, 1, 4)
    lp, cache = chunk(model, params, cache, second, 0, 32, 1, 4)
    lp2, cache = decode(model, params, cache, {1: (32, 4, second[32])})
    got = np.concatenate([lp, lp2[1:2]])
    assert np.abs(got - want(params, second, np.arange(33))).max() < EXACT
    counts = np.asarray(cache["moe_counts"])
    assert counts[0, 0, 5] == 2 and counts[0, 0, 6] == 0


def test_padding_and_idle_rows_change_nothing(monkeypatch):
    """A chunk padded to twice its length gives the same rows and leaves the
    same state and latent rows; a decode step leaves the slots with no row,
    and every cache block it does not write, bit for bit."""
    exact_attention(monkeypatch)
    model, params = build()
    toks = tokens_of(40, 3)
    plain_lp, plain = chunk(model, params, fresh_cache(model), toks, 0, 24, 1, 1)
    padded_lp, padded = chunk(model, params, fresh_cache(model), toks, 0, 24,
                              1, 1, pad_to=64)
    assert np.abs(plain_lp - padded_lp).max() < 1e-4    # the sums' order
    for leaf in ("state", "conv", "state_pos", "latent"):
        assert np.abs(np.asarray(plain[leaf], np.float32)
                      - np.asarray(padded[leaf], np.float32)).max() < 1e-4
    _, after = decode(model, params, plain, {3: (5, 30, 7)})
    for leaf in ("state", "conv"):
        for idle in (0, 1, 2):
            assert np.array_equal(np.asarray(after[leaf])[:, idle],
                                  np.asarray(plain[leaf])[:, idle])
    # one row written a latent layer: block 30, offset 5
    changed = np.argwhere(
        (np.asarray(after["latent"]) != np.asarray(plain["latent"])).any(-1))
    assert [list(c) for c in changed] == [[0, 30, 5], [1, 30, 5]]
    assert list(np.asarray(after["state_pos"])) == [0, 24, 0, 6]
    # slot 3 went on at position 5 with a state that stood at 0
    assert np.asarray(after["moe_counts"])[0, 0, 6] == 1
    # a step of idle rows alone: nothing moves but the call counts
    _, idle = decode(model, params, after, {})
    for leaf in ("state", "conv", "latent", "state_pos"):
        assert np.array_equal(np.asarray(idle[leaf]), np.asarray(after[leaf]))


def test_a_cut_of_the_stack_is_read_through_published_layers(monkeypatch):
    """The benchmark's file keeps published layer 0 and one later period:
    ``published_layers`` [0, 3, 4, 5] of the toy (period 3) is K+dense, K, K,
    M — not the K K M K the file's own indices would give — and the per-layer
    clamp lists are read by the published index."""
    exact_attention(monkeypatch)
    cut = dict(TINY, num_hidden_layers=4, published_layers=[0, 3, 4, 5],
               expert_swiglu_limit_list=[0, 9, 9, 0, 0, 0])
    model, params = build(cut)
    assert model.config.gqa_layers == (3,)
    assert [(r.kind, r.count) for r in model.runs] == [
        ("linear_dense", 1), ("linear", 2), ("mla", 1)]
    toks = tokens_of(30, 8)
    cache = fresh_cache(model)
    lp1, cache = chunk(model, params, cache, toks, 0, 16, 0, 2)
    lp2, cache = chunk(model, params, cache, toks, 16, 30, 0, 2)
    got = np.concatenate([lp1, lp2])
    assert np.abs(got - want(params, toks, np.arange(30), cut)).max() < EXACT
