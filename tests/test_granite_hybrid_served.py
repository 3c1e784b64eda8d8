"""The state-space toy (granite_hybrid_tiny.TINY) through ``EngineCore``'s
default path, test_hybrid_linear_served.py's cases for the second recurrence:
chunked prefill and decode against the reference, a freed slot taken by a new
request, what the engine refuses and switches off for a recurrent state,
idle slots bit for bit, and the bf16-state control."""

import jax.numpy as jnp
import numpy as np
import pytest

from granite_hybrid_tiny import ROUNDING, build, worst_delta
from hybrid_linear_tiny import BS, drain, engine, submit, tokens_of


def test_engine_serves_it_in_chunks_then_decodes_against_the_reference():
    """Two requests, one of three chunks (75 tokens, chunk 32 = two SSD
    pieces of 16): every generated position's top log-probabilities against
    the reference's full forward, and the counters — the ones the delta
    rule's layers write, with no entry of their own."""
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
    run = 75 + 20 + m["decode_rows_dispatched_total"]
    assert m["state_tokens_total"] == 5 * run
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0
    assert m["moe_router_picks_total"] == 3 * 6 * run
    assert 0 < m["moe_experts_touched_total"] <= 4 * 6 * (
        m["prefill_dispatches_total"] + m["decode_dispatches_total"])
    assert (m["state_layers"], m["cache_layers"]) == (5, 1)
    assert m["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 4      # 1 layer, K+V
    assert m["state_bytes_per_slot"] == 5 * (4 * 32 * 16 * 4 + 3 * 160 * 4)
    assert m["state_update_kernel"] == 0 and m["prefix_reuse"] == 0
    assert m["ahead_dispatches_total"] > 0


def test_a_freed_slot_taken_by_a_new_request_starts_from_zero():
    """One slot: the second request sits where the first sat, over the
    first's left-over state, tail and K/V, and answers as on a fresh engine
    (a reset at position 0)."""
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


def test_the_engine_refuses_what_would_lose_the_state_and_reuses_no_block():
    from dynamo_tpu.engine import EngineConfig, EngineCore

    model, params = build()
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
    assert core.config.enable_prefix_reuse            # asked for, and yet
    assert core.metrics()["prefix_reuse"] == 0
    for what in (lambda: core.gather_blocks_np([1]),
                 lambda: core.gather_blocks_device([1])):
        with pytest.raises(NotImplementedError, match="block movers"):
            what()
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
    before = {k: np.asarray(core.cache[k])[:, 1].copy()
              for k in ("state", "conv")}
    pos = int(np.asarray(core.cache["state_pos"])[1])
    assert np.abs(before["state"]).max() > 0 and pos >= 20
    done = len(got["stays"][0])
    for _ in range(5):
        core.step()
    assert done < len(got["stays"][0]) < 120
    for k, was in before.items():
        assert np.array_equal(np.asarray(core.cache[k])[:, 1], was)
    assert int(np.asarray(core.cache["state_pos"])[1]) == pos
    drain(core)


def test_a_state_held_in_bf16_is_another_model():
    """The negative control at a tiny size: ``state`` stored in bf16 between
    dispatches leaves the reference by far more than the float32 state."""
    model, params = build()
    control, _ = build(state_dtype=jnp.bfloat16)
    prompt = tokens_of(60, 9)
    deltas = {}
    for name, m in (("f32", model), ("bf16", control)):
        core = engine(m, params)
        got: dict = {}
        submit(core, "r", prompt, 24, got)
        drain(core)
        deltas[name] = worst_delta(params, prompt, got["r"])
    assert deltas["f32"] < ROUNDING
    assert deltas["bf16"] > 10 * deltas["f32"], deltas
