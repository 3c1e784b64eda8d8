"""The ZAYA toy (zaya_tiny.TINY) through ``EngineCore``'s default path,
test_hybrid_linear_served.py's cases for a model whose per-slot state is a
tail beside K/V rows in every layer: chunked prefill and decode against the
reference, a freed slot taken by a new request, what the engine refuses and
switches off, idle slots bit for bit, and the cache-one-precision-down
control."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_linear_tiny import BS, drain, engine, submit, tokens_of
from zaya_tiny import ROUNDING, build, worst_delta


def test_engine_serves_it_in_chunks_then_decodes_against_the_reference():
    """Two requests, one of three chunks (75 tokens, chunk 32): every
    generated position's top log-probabilities against the reference's full
    forward, and the counters: router picks = held picks + skip picks, the
    slot contract's counts and gauges, prefix reuse off."""
    model, params = build()
    core = engine(model, params)
    long, short = tokens_of(75, 1), tokens_of(20, 2)
    got: dict = {}
    submit(core, "long", long, 6, got)
    submit(core, "short", short, 10, got)
    drain(core)
    assert len(got["long"][0]) == 6 and len(got["short"][0]) == 10
    assert worst_delta(params, long, got["long"]) < ROUNDING
    assert worst_delta(params, short, got["short"]) < ROUNDING
    m = core.metrics()
    assert m["prefill_dispatches_total"] == 3 + 1
    # the last token of a request is sampled, not run
    run = 75 + 20 + m["decode_rows_dispatched_total"]
    assert m["state_tokens_total"] == 4 * run == m["moe_router_picks_total"]
    assert (m["moe_held_picks_total"] + m["moe_skip_picks_total"]
            == m["moe_router_picks_total"])
    assert 0 < m["moe_skip_picks_total"] < m["moe_held_picks_total"]
    assert m["moe_expert_layer_calls_total"] == 4 * (
        m["prefill_dispatches_total"] + m["decode_dispatches_total"])
    assert 0 < m["moe_experts_touched_total"] <= 4 * m["moe_expert_layer_calls_total"]
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0
    assert (m["state_layers"], m["cache_layers"]) == (4, 4)
    assert m["kv_bytes_per_token"] == 4 * 2 * 2 * 16 * 4      # 4 layers, K+V
    assert m["state_bytes_per_slot"] == 4 * (2 * 160 + 16) * 4
    assert m["state_update_kernel"] == 0 and m["prefix_reuse"] == 0
    assert m["ahead_dispatches_total"] > 0


def test_a_freed_slot_taken_by_a_new_request_starts_from_zero():
    """One slot: the second request sits where the first sat, over the
    first's left-over tails and K/V, and answers as on a fresh engine (a
    reset at position 0: its first token's convolutions and value shift see
    zeros, not the first request's last token)."""
    model, params = build()
    first, second = tokens_of(50, 5), tokens_of(33, 6)
    used: dict = {}
    core = engine(model, params, max_batch_size=1)
    submit(core, "first", first, 5, used)
    drain(core)
    assert float(jnp.abs(core.cache["state"]).max()) > 0     # left behind
    submit(core, "second", second, 5, used)
    drain(core)
    fresh: dict = {}
    core2 = engine(model, params, max_batch_size=1)
    submit(core2, "second", second, 5, fresh)
    drain(core2)
    assert used["second"][0] == fresh["second"][0]
    assert worst_delta(params, second, used["second"]) < ROUNDING
    m = core.metrics()
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0


def test_a_request_prefilled_while_other_slots_decode_equals_it_alone():
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
    assert 0 < len(busy["a"][0]) < 40                        # mid-decode
    submit(core, "late", late, 8, busy)
    drain(core)
    assert busy["late"][0] == alone["late"][0]
    assert worst_delta(params, late, busy["late"]) < ROUNDING
    assert core.metrics()["state_position_mismatches_total"] == 0


def test_the_engine_refuses_what_would_lose_the_tails_and_reuses_no_block():
    from dynamo_tpu.engine import EngineConfig, EngineCore

    model, params = build()
    for bad, name in ((dict(prefill_token_budget=64), "prefill_token_budget"),
                      (dict(unified_token_dispatch=True,
                            prefill_token_budget=64), "unified_token_dispatch"),
                      (dict(spec_tokens=2), "spec_tokens"),
                      (dict(num_host_blocks=8), "num_host_blocks"),
                      (dict(kv_persist_dir="/tmp/x"), "kv_persist_dir"),
                      (dict(cache_dtype="int8"), "cache_dtype=int8")):
        with pytest.raises(ValueError, match=name):
            EngineCore(model, params, EngineConfig(
                max_batch_size=2, max_model_len=64, block_size=BS,
                num_blocks=16, **bad), eos_token_ids=[])
    with pytest.raises(NotImplementedError, match="one pipeline stage"):
        model.partition_specs()
    core = engine(model, params)
    assert core.config.enable_prefix_reuse            # asked for, and yet
    assert core.metrics()["prefix_reuse"] == 0
    for what in (lambda: core.gather_blocks_np([1]),
                 lambda: core.gather_blocks_device([1])):
        with pytest.raises(NotImplementedError, match="block movers"):
            what()
    # the same document twice: nothing is served from a cached block (its
    # K/V rows would be there; the tail at the block's end is not)
    got: dict = {}
    doc = tokens_of(64, 8)
    submit(core, "one", doc + [3, 4], 3, got)
    drain(core)
    submit(core, "two", doc + [5, 6], 3, got)
    drain(core)
    assert core.metrics()["prompt_tokens_cached_total"] == 0
    assert core.prompt_tokens_computed == 66 + 66


def test_a_decode_dispatch_leaves_idle_slots_bit_for_bit():
    model, params = build()
    core = engine(model, params)
    got: dict = {}
    submit(core, "stays", tokens_of(10, 1), 120, got)
    submit(core, "ends", tokens_of(20, 2), 2, got)
    while len(got["ends"][0]) < 2 or core.slots[1] is not None:
        core.step()                       # read back, slot given up
    before = np.asarray(core.cache["state"])[:, 1].copy()
    pos = int(np.asarray(core.cache["state_pos"])[1])
    assert np.abs(before).max() > 0 and pos >= 20
    done = len(got["stays"][0])
    for _ in range(5):
        core.step()
    assert done < len(got["stays"][0]) < 120
    assert np.array_equal(np.asarray(core.cache["state"])[:, 1], before)
    assert int(np.asarray(core.cache["state_pos"])[1]) == pos
    drain(core)


def test_a_cache_kept_one_precision_down_is_another_model():
    """The negative control at a tiny size: K/V rows and tails rounded to
    float8 (e4m3) before they are kept leave the reference by far more than
    the served model — and so does rounding the tails alone."""
    f8 = lambda x: jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    tails_only = lambda x: f8(x) if x.ndim == 2 else x    # [B, W]; k, v are 4-d
    model, params = build()
    prompt = tokens_of(60, 9)
    deltas = {}
    for name, kept in (("served", None), ("down", f8), ("tails", tails_only)):
        m, _ = build(kept=kept)
        core = engine(m, params)
        got: dict = {}
        submit(core, "r", prompt, 24, got)
        drain(core)
        deltas[name] = worst_delta(params, prompt, got["r"])
    assert deltas["served"] < ROUNDING
    assert deltas["down"] > 100 * ROUNDING, deltas
    assert deltas["tails"] > 30 * ROUNDING, deltas
