"""The hybrid model with latent attending layers through ``EngineCore``'s
default path — a model that is ``private_cache_layout`` and
``recurrent_state`` at once: chunked prefill and decode against the
reference, a request prefilled while other slots decode, a freed slot taken
by a new request, what the engine counts for it and what it refuses."""

import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_linear_tiny import (BS, drain, engine, submit, tokens_of,
                                worst_delta)
from ling_tiny import TINY, build, exact_attention, want

EXACT = 2e-4


def test_engine_serves_it_in_chunks_then_decodes_against_the_reference(
        monkeypatch):
    """Two requests, one of two chunks (60 tokens, chunk 32; the next test's
    has three): every generated position's top log-probabilities against the
    reference's full forward, and the counters that say what each kind of
    layer did."""
    exact_attention(monkeypatch)
    model, params = build()
    core = engine(model, params)
    assert core._pool() is core.cache["latent"]
    long, short = tokens_of(60, 1), tokens_of(20, 2)
    got: dict = {}
    submit(core, "long", long, 6, got)
    submit(core, "short", short, 10, got)
    drain(core)
    assert len(got["long"][0]) == 6 and len(got["short"][0]) == 10
    assert worst_delta(params, long, got["long"], TINY, want) < EXACT
    assert worst_delta(params, short, got["short"], TINY, want) < EXACT
    m = core.metrics()
    assert m["prefill_dispatches_total"] == 2 + 1
    # the last token of a request is sampled, not run
    run = 60 + 20 + m["decode_rows_dispatched_total"]
    assert m["state_tokens_total"] == 4 * run          # four KDA layers
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0
    assert m["moe_router_picks_total"] == 2 * 5 * run  # top-2, 5 expert layers
    assert m["moe_expert_layer_calls_total"] == 5 * (
        m["prefill_dispatches_total"] + m["decode_dispatches_total"])
    assert (m["state_layers"], m["cache_layers"]) == (4, 2)
    # a token's cache: one row of 128 lanes (32 + 8, padded) a latent layer
    assert m["kv_bytes_per_token"] == 2 * 128 * 4
    assert m["state_bytes_per_slot"] == 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert m["state_update_kernel"] == 0               # off the TPU
    assert m["prefix_reuse"] == 0 and m["ahead_dispatches_total"] > 0
    # every decode row's context is fetched (no prefix is shared)
    assert m["attn_fetched_tokens_total"] >= m["attn_context_tokens_total"] > 0
    assert set(core.attention_impls()) == {"decode", "prefill"}
    assert "mla_dense_decode" in core.attention_impls()["decode"][1]


def test_a_request_prefilled_while_other_slots_decode_equals_it_alone(
        monkeypatch):
    """The late request's three chunks alternate with the decode steps of
    two running requests: its tokens and log-probabilities are those of the
    same request alone, state and latent rows alike."""
    exact_attention(monkeypatch)
    model, params = build()
    late = tokens_of(90, 7)
    alone: dict = {}
    core = engine(model, params)
    submit(core, "late", late, 8, alone)
    drain(core)

    busy: dict = {}
    core = engine(model, params)
    submit(core, "a", tokens_of(12, 3), 40, busy)
    submit(core, "b", tokens_of(30, 4), 40, busy)
    for _ in range(6):
        core.step()
    assert 0 < len(busy["a"][0]) < 40                  # mid-decode
    submit(core, "late", late, 8, busy)
    drain(core)
    assert busy["late"][0] == alone["late"][0]
    for (cands_a, cands_b) in zip(alone["late"][1], busy["late"][1]):
        assert [t for t, _ in cands_a] == [t for t, _ in cands_b]
        assert max(abs(x - y) for (_, x), (_, y) in zip(cands_a, cands_b)) < 1e-4
    assert worst_delta(params, late, busy["late"], TINY, want) < EXACT
    assert core.metrics()["state_position_mismatches_total"] == 0


def test_a_freed_slot_taken_by_a_new_request_starts_from_zero(monkeypatch):
    """One slot: the second request sits where the first sat, over the
    first's left-over state and latent rows, and answers as on a fresh
    engine."""
    exact_attention(monkeypatch)
    model, params = build()
    first, second = tokens_of(50, 5), tokens_of(33, 6)
    used: dict = {}
    core = engine(model, params, max_batch_size=1)
    submit(core, "first", first, 5, used)
    drain(core)
    assert float(jnp.abs(core.cache["state"]).max()) > 0     # left behind
    assert float(jnp.abs(core.cache["latent"]).max()) > 0
    submit(core, "second", second, 5, used)
    drain(core)
    fresh: dict = {}
    core2 = engine(model, params, max_batch_size=1)
    submit(core2, "second", second, 5, fresh)
    drain(core2)
    assert used["second"][0] == fresh["second"][0]
    assert worst_delta(params, second, used["second"], TINY, want) < EXACT
    m = core.metrics()
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0


def test_the_engine_refuses_what_would_lose_the_state_or_move_latent_blocks():
    from dynamo_tpu.engine import EngineConfig, EngineCore

    model, params = build()
    assert model.private_cache_layout and model.recurrent_state
    assert model.prefix_blocks_sizes_forward and model.pool_leaf == "latent"
    for bad, name in ((dict(prefill_token_budget=64), "prefill_token_budget"),
                      (dict(unified_token_dispatch=True,
                            prefill_token_budget=64), "unified_token_dispatch"),
                      (dict(spec_tokens=2), "spec_tokens"),
                      (dict(num_host_blocks=8), "num_host_blocks"),
                      (dict(cache_dtype="int8"), "cache_dtype=int8")):
        with pytest.raises(ValueError, match=name):
            EngineCore(model, params, EngineConfig(
                max_batch_size=2, max_model_len=64, block_size=BS,
                num_blocks=16, **bad), eos_token_ids=[])
    core = engine(model, params)
    assert core.metrics()["prefix_reuse"] == 0
    for what in (lambda: core.gather_blocks_np([1]),
                 lambda: core.gather_blocks_device([1])):
        with pytest.raises(NotImplementedError, match="block movers"):
            what()
    # a GQA hybrid keeps the K/V pool and the flash kernels' block counts
    from hybrid_linear_tiny import build as build_gqa

    gqa, gqa_params = build_gqa()
    other = engine(gqa, gqa_params)
    assert gqa.pool_leaf == "kv" and other._pool() is other.cache["kv"]
    assert not hasattr(gqa, "attention_impls")
    assert not gqa.prefix_blocks_sizes_forward
    assert gqa.moe_count_keys == core.model.moe_count_keys
    assert other.cache["moe_counts"].shape[-1] == 7


def test_a_decode_dispatch_leaves_idle_slots_bit_for_bit():
    """Slot 1 holds a finished request's state; slot 0 decodes.  After more
    decode steps slot 1's ``state`` and ``conv`` are what they were, and so
    are the latent rows of its blocks."""
    model, params = build()
    core = engine(model, params)
    got: dict = {}
    submit(core, "stays", tokens_of(10, 1), 120, got)
    submit(core, "ends", tokens_of(20, 2), 2, got)
    while len(got["ends"][0]) < 2 or core.slots[1] is not None:
        core.step()                       # read back, slot given up
    slot = 1
    before = {k: np.asarray(core.cache[k])[:, slot].copy()
              for k in ("state", "conv")}
    pos = int(np.asarray(core.cache["state_pos"])[slot])
    assert np.abs(before["state"]).max() > 0 and pos >= 20
    done = len(got["stays"][0])
    for _ in range(5):
        core.step()
    assert done < len(got["stays"][0]) < 120
    for k, was in before.items():
        assert np.array_equal(np.asarray(core.cache[k])[:, slot], was)
    assert int(np.asarray(core.cache["state_pos"])[slot]) == pos
    drain(core)
