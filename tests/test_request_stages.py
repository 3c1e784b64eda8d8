"""PR 43: what a first token waits for.  A request is stamped at every
boundary it crosses between the socket and its first token, on one clock;
the engine keeps the two stages behind the slot as counters and the prefill
backlog where the scheduler sees it (docs/observability.md, "A request's
stages").  The same stamps as spans, and the front end's two ends:
test_request_stage_spans.py; a turn's launch and readback by class:
test_step_class_split.py.  Three small files and not one, so that under
``-n 6 --dist loadfile`` (work units go out by their number of tests, the
largest first) none of them runs beside the timed rehearsals of
tests/cellbench_tests."""

import time

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine import counters as engine_counters
from dynamo_tpu.engine.request import EngineRequest, RequestState
from dynamo_tpu.llm.protocols import (LLMEngineOutput, SamplingOptions,
                                      StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs.timeline import step_timeline


def test_the_stamps_and_the_span_plane_share_one_clock():
    """``submitted_at`` and every stage stamp read ``perf_counter``; dtspan
    and the profiler's ``t_mono_ns`` read ``monotonic_ns``.  On Linux both
    are CLOCK_MONOTONIC: were they not, the stamps would have to move."""
    perf, mono = (time.get_clock_info(c) for c in ("perf_counter", "monotonic"))
    assert perf.implementation == mono.implementation
    for _ in range(3):
        a = time.monotonic_ns()
        p = time.perf_counter()
        b = time.monotonic_ns()
        assert a - 1_000 <= p * 1e9 <= b + 1_000


# ------------------------------------------------------------- a real engine
@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(model, params, **kw):
    cfg = dict(max_batch_size=4, max_model_len=128, block_size=8,
               num_blocks=64, prefill_buckets=[16, 32, 64, 128],
               prefill_chunk_tokens=16)
    cfg.update(kw)
    return EngineCore(model, params, EngineConfig(**cfg))


def submit(core, rid, prompt_len, max_tokens, seed=0, trace=None, **kw):
    outs = []
    prompt = np.random.RandomState(seed).randint(1, 200, size=prompt_len)
    req = EngineRequest(
        rid, [int(t) for t in prompt], SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=max_tokens), outs.append, trace=trace, **kw)
    core.submit(req)
    return req, outs


def run_dry(core, limit=400):
    for _ in range(limit):
        if not core.step():
            return
    raise AssertionError("the engine did not drain")


@pytest.fixture()
def stages(tiny):
    """Two 40-token prompts admitted in one turn and prefilled in chunks of
    16, then the first prompt again (a prefix hit of four blocks)."""
    engine_counters.reset()
    step_timeline.reset()
    core = make_core(*tiny)
    a, _ = submit(core, "a", 40, 5, seed=1)
    b, _ = submit(core, "b", 40, 5, seed=2)
    core.step()             # admits both: two stand ready, a's chunk goes
    first = (core.counts.prefill_ready_rows_total,
             core.counts.prefill_dispatches_total)
    run_dry(core)
    c, outs = submit(core, "c", 40, 4, seed=1)
    run_dry(core)
    return core, (a, b, c), first, outs


def test_the_three_stages_add_up_to_the_engine_ttft(stages):
    core, reqs, _, _ = stages
    for req in reqs:
        assert req.submitted_at <= req.admitted_at <= req.first_issue_at \
            <= req.first_token_at
        ttft = req.first_token_at - req.submitted_at
        turn_wait = req.first_issue_at - req.admitted_at
        span = req.first_token_at - req.first_issue_at
        assert req.queue_wait_s + turn_wait + span == pytest.approx(
            ttft, rel=1e-9, abs=1e-9)
        assert 0 <= req.first_issue_step <= req.first_token_step
    a, b, c = reqs
    assert (a.prefill_chunks, b.prefill_chunks, c.prefill_chunks) == (3, 3, 1)
    assert c.cached_tokens == 32            # the prefix hit
    assert a.first_issue_step == 0
    # served in order of admission: b's first chunk goes after a's last
    assert b.first_issue_step > a.first_issue_step + 1
    assert b.first_issue_at - b.admitted_at > a.first_issue_at - a.admitted_at
    m = core.metrics()
    assert m["first_tokens_total"] == 3
    assert m["turn_wait_seconds_total"] == pytest.approx(
        sum(r.first_issue_at - r.admitted_at for r in reqs), rel=1e-9)
    assert m["prefill_span_seconds_total"] == pytest.approx(
        sum(r.first_token_at - r.first_issue_at for r in reqs), rel=1e-9)
    assert sum(r.queue_wait_s for r in reqs) + m["turn_wait_seconds_total"] \
        + m["prefill_span_seconds_total"] == pytest.approx(
            m["first_token_seconds_total"], rel=1e-9)
    # what /metrics renders: the process's engines summed, here this one
    totals = engine_counters.engine_totals()
    assert totals.turn_wait_seconds_total == m["turn_wait_seconds_total"]
    assert totals.prefill_span_seconds_total \
        == m["prefill_span_seconds_total"]


def test_the_prefill_backlog_is_counted_at_the_dispatch(stages):
    core, _, first, _ = stages
    assert first == (2, 1)          # two stood ready when a's chunk went
    m = core.metrics()
    # a's three chunks with b ready behind them, b's three alone, c's one
    assert m["prefill_dispatches_total"] == 7
    assert m["prefill_ready_rows_total"] == 3 * 2 + 3 * 1 + 1
    totals = engine_counters.engine_totals()
    assert totals.prefill_ready_rows_total == m["prefill_ready_rows_total"]
    assert totals.prefill_dispatches_total == m["prefill_dispatches_total"]


def test_every_emitted_output_carries_its_dispatch_s_clock_read(stages):
    _, (_, _, c), _, outs = stages
    stamps = [o.emitted_at for o in outs]
    assert len(stamps) == 4 and all(stamps)
    assert stamps == sorted(stamps)
    assert c.first_issue_at <= stamps[0] <= c.first_token_at


def test_the_emit_stamp_stays_off_the_wire_and_out_of_equality():
    from dynamo_tpu.runtime import serde

    serde.register_llm_types()
    out = LLMEngineOutput(token_ids=[7])
    assert out.emitted_at == 0.0
    out.emitted_at = 12.5
    assert b"emitted_at" not in serde.dumps(out)
    assert serde.loads(serde.dumps(out)).emitted_at == 0.0
    assert out == LLMEngineOutput(token_ids=[7])


def test_a_remote_prefill_request_waited_all_of_it(tiny):
    """No dispatch of this engine carries it before its first token (K/V
    and token come from a prefill worker): the wait is turn wait, the three
    stages still add up."""
    core = make_core(*tiny)
    req, outs = submit(core, "rp", 24, 3, remote_prefill=True)
    core.step()
    assert req.state is RequestState.REMOTE_PREFILL and not req.first_issue_at
    core.complete_remote_prefill("rp", 5)
    run_dry(core)
    assert sum(len(o.token_ids) for o in outs) == 3
    assert req.first_issue_at == req.first_token_at > req.admitted_at
    assert req.first_issue_step == req.first_token_step
    m = core.metrics()
    assert m["prefill_span_seconds_total"] == 0.0
    assert req.queue_wait_s + m["turn_wait_seconds_total"] == pytest.approx(
        m["first_token_seconds_total"], rel=1e-9)
    assert outs[0].emitted_at > 0
