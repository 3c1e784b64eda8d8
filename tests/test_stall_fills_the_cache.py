"""What failed six requests of ``mistral-7b.chat-open`` in PR 32's check, as
far as PR 33's step 0 could tell: not an exception of the step, but a stall.

Admission reserves blocks for a request's prompt, none for its answer.  An
open-loop cell that peaks at two thirds of its cache stands a hole of the
machine of a few seconds; behind one of twelve (the load generator sends the
hole's arrivals at once) every waiting prompt is admitted, the cache fills,
and rows that need one more block end at ``length`` short of ``max_tokens``:
``status: ok`` at the client, ``failed`` in the benchmark, counted in
``requests_cut_short_total``, and no line in the log.  Made by hand on the
chip, on the parent's tree, that was 5 of 122 requests (PERF.md §6, PR 33).

The other way one event becomes several failed requests is a step that
raises: ``AsyncLLMEngine._run`` fails every request in flight.  It did not
happen (no ``engine step failed`` in 14 runs), and what it may cost is
pinned here too: the requests of that instant, not the engine."""

import asyncio

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import (BackendInput, FinishReason,
                                      SamplingOptions, StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.runtime.engine import Context


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(tiny, **kw):
    cfg = dict(max_batch_size=8, max_model_len=128, block_size=8,
               num_blocks=24, prefill_buckets=[16, 32, 64, 128],
               prefill_chunk_tokens=16)
    cfg.update(kw)
    return EngineCore(*tiny, EngineConfig(**cfg))


def prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 200, size=n)]


def submit(core, rid, prompt_len, max_tokens, seed=0):
    outs = []
    core.submit(EngineRequest(
        rid, prompt(prompt_len, seed),
        SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=max_tokens, ignore_eos=True), outs.append))
    return outs


def run_dry(core, limit=600):
    for _ in range(limit):
        if not core.step():
            return
    raise AssertionError("the engine did not drain")


def tokens(outs):
    return sum(len(o.token_ids) for o in outs)


def test_arrivals_behind_a_stall_fill_the_cache_and_rows_end_short_at_length(
        tiny):
    """Eight requests arrive while the engine does not step (the stall) and
    are admitted in one turn: 8 x 2 prompt blocks of 24.  Their answers
    need 8 x 3 more.  Some end short at ``length``, each counted once; none
    ends in error, nothing raises, no block leaks, and the engine serves
    the next request whole."""
    core = make_core(tiny)
    streams = [submit(core, f"r{i}", 14, 24, seed=i) for i in range(8)]
    run_dry(core)
    assert all(s[-1].finish_reason == FinishReason.LENGTH for s in streams)
    short = [s for s in streams if tokens(s) < 24]
    assert 0 < len(short) < 8
    m = core.metrics()
    assert m["requests_cut_short_total"] == len(short)
    assert m["requests_finished_total"] == 8
    assert m["kv_active_blocks"] == 0 and core._inflight is None
    after = submit(core, "after", 14, 24)
    run_dry(core)
    assert tokens(after) == 24
    assert core.metrics()["requests_cut_short_total"] == len(short)


def test_a_step_that_raises_once_fails_what_was_in_flight_and_no_more(tiny):
    """One exception out of ``core.step()`` that built nothing: the engine
    thread fails the requests of that instant (``error``) and goes on; the
    next request is served whole and ``engine.failed`` stays unresolved."""
    core = make_core(tiny, num_blocks=64)
    step, raised = core.step, []

    def step_raising_once():
        if not raised and any(
                r is not None and r.generated >= 3 for r in core.slots):
            raised.append(True)
            raise RuntimeError("injected: one step fails")
        return step()

    core.step = step_raising_once
    engine = AsyncLLMEngine(core).start()

    async def ask(rid, max_tokens):
        outs = []
        ctx = Context(BackendInput(
            token_ids=prompt(14, 1), sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=max_tokens, ignore_eos=True)),
            id=rid)
        async for out in engine.generate(ctx):
            outs.append(out)
        return outs

    async def drive():
        first = await asyncio.gather(ask("a", 40), ask("b", 40))
        # ``fail_all`` ends with a drain of the waiting queue: a request
        # that arrives while it still runs counts as queued, and fails too
        await asyncio.sleep(0.3)
        return first, await ask("c", 12)

    try:
        first, later = asyncio.run(asyncio.wait_for(drive(), 120))
    finally:
        engine.shutdown()
    assert raised
    assert [s[-1].finish_reason for s in first] == [FinishReason.ERROR] * 2
    assert all(tokens(s) < 40 for s in first)
    assert later[-1].finish_reason == FinishReason.LENGTH
    assert tokens(later) == 12
    assert not engine.failed.done()
    assert core.metrics()["kv_active_blocks"] == 0
