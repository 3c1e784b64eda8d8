"""The hand-over of outputs from the engine thread to the event loops
(``engine/async_engine.py``): a request's ``emit`` collects, the core says
where a batch is complete (``EngineCore.flush_outputs``), and one
``call_soon_threadsafe`` per loop carries it across; on the other side a
stream awaits its queue, with one task a stream and none a token.  No
timing is asserted: counts, order and finishes are."""

import asyncio
import threading

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.engine import async_engine
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import (BackendInput, FinishReason,
                                      SamplingOptions, StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.runtime.engine import Context

WAIT_S = 120.0


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(tiny, **kw):
    cfg = dict(max_batch_size=8, max_model_len=128, block_size=8,
               num_blocks=160, prefill_buckets=[16, 32, 64, 128])
    cfg.update(kw)
    return EngineCore(*tiny, EngineConfig(**cfg), eos_token_ids=[])


def prompt(seed, n=9):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 200, size=n)]


def context(seed, max_tokens):
    return Context(BackendInput(
        token_ids=prompt(seed), sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=max_tokens, ignore_eos=True)))


async def collect(engine, ctx):
    return [out async for out in engine.generate(ctx)]


def tokens(outs):
    return [t for o in outs for t in o.token_ids]


def assert_one_finish(outs, reason):
    assert [o.finished for o in outs].count(True) == 1
    assert outs[-1].finish_reason == reason


def reference(tiny, seeds, max_tokens):
    """The same requests through a core driven directly: a plain callable
    as ``emit``, no hook.  Returns each request's outputs and the core."""
    core = make_core(tiny)
    outs = {s: [] for s in seeds}
    for s in seeds:
        core.submit(EngineRequest(
            f"r{s}", prompt(s), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=max_tokens, ignore_eos=True),
            outs[s].append))
    while core.step():
        pass
    return outs, core


async def quiet(core):
    for _ in range(int(WAIT_S / 0.01)):
        if not core.has_work():
            return
        await asyncio.sleep(0.01)
    raise AssertionError("the engine did not go quiet")


def count_finishes_with_output(core):
    """Wrap ``_finish_dispatch``: how many calls emitted something (here
    every output carries a token, so ``tokens_generated`` tells)."""
    calls = []
    real = core._finish_dispatch

    def counted(rec):
        before = core.counts.tokens_generated
        real(rec)
        calls.append(core.counts.tokens_generated - before)

    core._finish_dispatch = counted
    return calls


# ---------------------------------- (a) one hop a dispatch, not one a row
def test_a_dispatch_s_outputs_cross_in_one_hop(tiny):
    seeds, n = list(range(1, 9)), 24

    async def go():
        core = make_core(tiny)
        calls = count_finishes_with_output(core)
        engine = AsyncLLMEngine(core).start()
        try:
            got = await asyncio.wait_for(asyncio.gather(
                *(collect(engine, context(s, n)) for s in seeds)), WAIT_S)
            await quiet(core)
        finally:
            engine.shutdown()
        return got, calls, core.metrics()

    got, calls, m = asyncio.run(go())
    produced = [c for c in calls if c]
    assert m["emit_hops_total"] == len(produced)
    assert m["outputs_emitted_total"] == sum(len(outs) for outs in got) \
        == sum(produced) == len(seeds) * n
    # the streams decoded together: far fewer hops than outputs
    assert m["emit_hops_total"] * 3 < m["outputs_emitted_total"]
    want, _ = reference(tiny, seeds, n)
    for s, outs in zip(seeds, got):
        assert tokens(outs) == tokens(want[s])
        assert_one_finish(outs, FinishReason.LENGTH)


# ------------------------------ (b) two event loops: one hop a loop each
def test_streams_on_two_event_loops_each_get_their_own(tiny, monkeypatch):
    n = 30
    seeds = {"x": [11, 12, 13], "y": [21, 22, 23]}
    delivered = []        # (the loop it ran on, the batch)
    real = async_engine._deliver

    def spy(batch):
        delivered.append((asyncio.get_running_loop(), list(batch)))
        real(batch)

    monkeypatch.setattr(async_engine, "_deliver", spy)
    engine = AsyncLLMEngine(make_core(tiny)).start()
    together = threading.Barrier(2, timeout=WAIT_S)
    got, loops = {}, {}

    def on_a_loop_of_its_own(name):
        async def go():
            loops[name] = asyncio.get_running_loop()
            together.wait()
            return await asyncio.wait_for(asyncio.gather(
                *(collect(engine, context(s, n)) for s in seeds[name])),
                WAIT_S)
        got[name] = asyncio.run(go())

    threads = [threading.Thread(target=on_a_loop_of_its_own, args=(name,))
               for name in seeds]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT_S)
        assert not any(t.is_alive() for t in threads)
    finally:
        engine.shutdown()

    want, _ = reference(tiny, seeds["x"] + seeds["y"], n)
    for name in seeds:
        for s, outs in zip(seeds[name], got[name]):
            assert tokens(outs) == tokens(want[s])
            assert_one_finish(outs, FinishReason.LENGTH)
        mine = [batch for loop, batch in delivered if loop is loops[name]]
        # a loop was handed its own streams' outputs and nobody else's
        assert sum(len(b) for b in mine) == sum(len(o) for o in got[name])
        assert {id(out) for b in mine for _, out in b} \
            == {id(out) for outs in got[name] for out in outs}
        # a dispatch holds a row once: two outputs of one stream in a batch
        # would be a flush that missed the end of a dispatch's host work
        for b in mine:
            assert len({put for put, _ in b}) == len(b)
        assert any(len(b) > 1 for b in mine)
    m = engine.core.metrics()
    assert m["emit_hops_total"] == len(delivered)
    assert m["outputs_emitted_total"] == 2 * 3 * n


# --------------------------------- (c) cancellation keeps its meaning
def test_a_stopped_context_and_a_dropped_stream_both_abort(tiny):
    async def go():
        core = make_core(tiny)
        engine = AsyncLLMEngine(core).start()
        try:
            ctx = context(31, 100)
            got = []
            async for out in engine.generate(ctx):
                got.append(out)
                if len(got) == 3:
                    ctx.stop_generating()
            await quiet(core)
            assert_one_finish(got, FinishReason.CANCELLED)
            assert len(tokens(got)) < 100
            assert all(s is None for s in core.slots)
            assert core.block_manager.active_blocks == 0
            # nothing of the stream is left on the loop
            assert asyncio.all_tasks() == {asyncio.current_task()}

            # a Context stopped before its first token
            ctx = context(32, 100)
            ctx.kill()
            got = await asyncio.wait_for(collect(engine, ctx), WAIT_S)
            assert_one_finish(got, FinishReason.CANCELLED)

            # the consumer goes away: the request is aborted all the same
            finished = core.metrics()["requests_finished_total"]
            agen = engine.generate(context(33, 100))
            async for out in agen:
                break
            await agen.aclose()
            await quiet(core)
            assert core.metrics()["requests_finished_total"] == finished + 1
            assert core.metrics()["tokens_generated"] < 250
            assert core.block_manager.active_blocks == 0
            assert asyncio.all_tasks() == {asyncio.current_task()}
        finally:
            engine.shutdown()

    asyncio.run(go())


# --------------------- (d) fail_all leaves nothing in a sleeping outbox
def test_a_failed_step_reaches_every_stream_as_an_error(tiny):
    async def go():
        # two slots, four streams: two decode, two wait in the queue
        core = make_core(tiny, max_batch_size=2)
        real, broken = core._step_inner, threading.Event()

        def step_inner():
            if broken.is_set():
                broken.clear()
                raise RuntimeError("injected: the step failed")
            return real()

        core._step_inner = step_inner
        engine = AsyncLLMEngine(core).start()
        try:
            streams = [asyncio.ensure_future(collect(engine, context(s, 100)))
                       for s in (41, 42, 43, 44)]
            for _ in range(int(WAIT_S / 0.01)):
                if core.metrics()["tokens_generated"] >= 6:
                    break
                await asyncio.sleep(0.01)
            broken.set()
            got = await asyncio.wait_for(asyncio.gather(*streams), WAIT_S)
            assert not engine.failed.done()    # not a build: it serves on
            after = await asyncio.wait_for(
                collect(engine, context(45, 4)), WAIT_S)
        finally:
            engine.shutdown()
        return got, after, engine

    got, after, engine = asyncio.run(go())
    for outs in got:
        assert_one_finish(outs, FinishReason.ERROR)
    assert sum(1 for outs in got if not tokens(outs)) >= 2   # the queued
    assert len(tokens(after)) == 4
    assert not engine._outbox


# ----------------------------------- (e) a task a stream, none a token
def test_a_stream_costs_a_task_not_a_task_a_token(tiny):
    seeds, n = [51, 52, 53, 54], 40
    made = []

    async def go():
        loop = asyncio.get_running_loop()

        def counting(loop, coro, **kw):
            task = asyncio.Task(coro, loop=loop, **kw)
            made.append(task)
            return task

        engine = AsyncLLMEngine(make_core(tiny)).start()
        loop.set_task_factory(counting)
        try:
            return await asyncio.wait_for(asyncio.gather(
                *(collect(engine, context(s, n)) for s in seeds)), WAIT_S)
        finally:
            loop.set_task_factory(None)
            engine.shutdown()

    got = asyncio.run(go())
    assert all(len(tokens(outs)) == n for outs in got)
    # gather's task and the stream's one watcher of its Context, a stream;
    # wait_for's own
    assert len(made) <= 2 * len(seeds) + 2, len(made)


# ------------------- (f) a core driven directly: called once a row, no hook
def test_a_plain_emit_callable_is_called_once_a_row(tiny):
    seeds, n = [61, 62, 63], 12
    outs, core = reference(tiny, seeds, n)
    assert core.flush_outputs is None
    for s in seeds:
        assert len(outs[s]) == n == len(tokens(outs[s]))
        assert_one_finish(outs[s], FinishReason.LENGTH)
    m = core.metrics()
    assert m["emit_hops_total"] == 0 and m["outputs_emitted_total"] == 0
    assert m["tokens_generated"] == len(seeds) * n
    core.close()
