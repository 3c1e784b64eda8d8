"""Dispatch-ahead decode (PR 29): the engine issues dispatch N+1 before it
reads dispatch N back (``EngineCore._settle``).  These tests hold it to the
serial step — the same code with the in-flight slot forced empty — token
for token, and pin the rules: at most one dispatch in flight, never a
second decode ahead of a ready prefill, idle means nothing in flight, and
what a stop found one dispatch late may and may not do."""

import asyncio

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.engine import counters as engine_counters
from dynamo_tpu.engine.grammar import JsonGrammar
from dynamo_tpu.engine.request import EngineRequest, RequestState
from dynamo_tpu.llm.http.metrics import Metrics
from dynamo_tpu.llm.protocols import (BackendInput, FinishReason,
                                      SamplingOptions, StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.runtime.engine import Context

EOS = 2


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(tiny, serial=False, eos=None, grammar=None, mesh=None, **kw):
    cfg = dict(max_batch_size=4, max_model_len=128, block_size=8,
               num_blocks=64, prefill_buckets=[16, 32, 64, 128])
    cfg.update(kw)
    core = EngineCore(*tiny, EngineConfig(**cfg), eos_token_ids=eos,
                      grammar=grammar, mesh=mesh)
    if serial:
        # the reference: no dispatch ever stays in flight (the eligibility
        # rule answers no), so every turn is issue, read back, finish
        core._may_stay_in_flight = lambda rec: False
    return core


def prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 200, size=n)]


def submit(core, rid, toks, sampling=None, **stops):
    outs = []
    core.submit(EngineRequest(
        rid, list(toks), sampling or SamplingOptions(temperature=0.0),
        StopConditions(**stops), outs.append))
    return outs


def run(core, limit=2000):
    """Step until idle, holding every turn to the invariants."""
    for _ in range(limit):
        worked = core.step()
        fl = core._inflight
        # at most one in flight, and only where a decode comes next
        assert fl is None or fl.kind == "decode_multi" or core._decode_follows()
        if not worked:
            # rule 3: idle is idle
            assert not core.has_work() and fl is None
            return
    raise AssertionError("the engine did not drain")


def stream(outs):
    """(tokens, logprobs, top_logprobs, finish reasons) of one request."""
    return ([t for o in outs for t in o.token_ids],
            [lp for o in outs for lp in (o.logprobs or [])],
            [tl for o in outs for tl in (o.top_logprobs or [])],
            [o.finish_reason for o in outs if o.finish_reason is not None])


def quiescent(core):
    return (all(s is None for s in core.slots)
            and core.block_manager.active_blocks == 0
            and not core.block_manager._reserved
            and core._inflight is None)


def assert_same_streams(live, ref):
    for a, b in zip(live, ref, strict=True):
        assert a[0] == b[0] and a[3] == b[3]
        assert a[2] == b[2]
        np.testing.assert_array_equal(a[1], b[1])
        assert len(a[0]) == len(a[1])


# greedy and per-request seeds do not depend on who shares the batch, so
# they must not depend on when a row joins it either; unseeded sampling
# draws from the dispatch's key by row and is only held to its own schedule
SAMPLINGS = {
    "greedy": dict(temperature=0.0),
    "seeded": dict(temperature=0.8, top_p=0.9, seed=1234),
}
# the cache as the model's dtype and as int8 K/V with per-block scales
# (``--kv-cache-dtype int8``): a carried row reads what the decode in
# flight wrote, quantised or not
CACHES = pytest.mark.parametrize("cache_dtype", [None, "int8"],
                                 ids=["native", "int8"])


# ----------------------------- (a) a full batch running to max_tokens
@pytest.mark.parametrize("budget", [0, 64], ids=["legacy", "ragged"])
@pytest.mark.parametrize("rows", [1, 4])
@pytest.mark.parametrize("kind", sorted(SAMPLINGS))
@CACHES
def test_streams_equal_the_serial_engine(tiny, cache_dtype, kind, rows,
                                         budget):
    got = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, prefill_chunk_tokens=16,
                         prefill_token_budget=budget,
                         cache_dtype=cache_dtype)
        outs = [submit(core, f"r{i}", prompt(9 + 11 * i, i),
                       SamplingOptions(logprobs=True, top_logprobs=2,
                                       **SAMPLINGS[kind]),
                       max_tokens=6 + 3 * i)
                for i in range(rows)]
        run(core)
        got[serial] = [stream(o) for o in outs]
        assert quiescent(core)
        m = core.metrics()
        assert m["ahead_discards_total"] == 0
        if serial:
            assert m["ahead_dispatches_total"] == 0
        else:
            assert m["ahead_dispatches_total"] >= 4
            assert m["ahead_dispatches_total"] <= m["decode_dispatches_total"]
    assert_same_streams(got[False], got[True])
    assert all(s[3] == [FinishReason.LENGTH] for s in got[False])


def test_unseeded_sampling_keeps_its_key_sequence(tiny):
    """One split a dispatch, in dispatch order: a lone request, whose
    schedule is the serial one, draws the same tokens."""
    got = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, prefill_chunk_tokens=16)
        outs = submit(core, "r", prompt(40, 3),
                      SamplingOptions(temperature=0.7, top_p=0.9),
                      max_tokens=12)
        run(core)
        got[serial] = stream(outs)
    assert got[False][0] == got[True][0] and len(got[True][0]) == 12


# --------------- (b) rows joining from prefill mid-stream, chunked prompts too
@pytest.mark.parametrize("budget", [0, 64], ids=["legacy", "ragged"])
@pytest.mark.parametrize("kind", sorted(SAMPLINGS))
@CACHES
def test_rows_that_join_mid_stream(tiny, cache_dtype, kind, budget):
    got = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, prefill_chunk_tokens=16,
                         prefill_token_budget=budget,
                         cache_dtype=cache_dtype)
        sampling = SamplingOptions(logprobs=True, top_logprobs=2,
                                   **SAMPLINGS[kind])
        outs = [submit(core, "a", prompt(9, 1), sampling, max_tokens=30),
                submit(core, "b", prompt(20, 2), sampling, max_tokens=25)]
        for _ in range(6):
            core.step()
        # a three-chunk prompt and a short one arrive while two rows decode
        outs.append(submit(core, "c", prompt(40, 3), sampling, max_tokens=9))
        for _ in range(3):
            core.step()
        outs.append(submit(core, "d", prompt(11, 4), sampling, max_tokens=14))
        run(core)
        got[serial] = [stream(o) for o in outs]
        assert quiescent(core)
        m = core.metrics()
        assert m["ahead_discards_total"] == 0
        if not serial:
            # the decodes behind a prefill are ahead too: the chain holds
            assert m["ahead_dispatches_total"] >= 0.8 * m["decode_dispatches_total"]
    assert_same_streams(got[False], got[True])
    assert [len(s[0]) for s in got[False]] == [30, 25, 9, 14]


# ------------------------------------------------------- (c) a stop found late
def reference_tokens(tiny, toks, n):
    core = make_core(tiny, serial=True)
    outs = submit(core, "ref", toks, max_tokens=n)
    run(core)
    return stream(outs)[0]


@pytest.mark.parametrize("how", ["eos", "stop_token"])
def test_a_late_stop_emits_nothing_past_it_and_holds_its_blocks(tiny, how):
    toks = prompt(13, 5)
    ref = reference_tokens(tiny, toks, 24)
    # a token that first appears a few decode steps in, past a block edge
    at = next(i for i in range(9, len(ref)) if ref[i] not in ref[:i])
    stop = ref[at]
    kw = dict(eos=[stop]) if how == "eos" else {}
    stops = {} if how == "eos" else dict(stop_token_ids=[stop])
    want = FinishReason.EOS if how == "eos" else FinishReason.STOP

    committed = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, **kw)
        outs = submit(core, "a", toks, max_tokens=24, **stops)
        other = submit(core, "b", prompt(10, 6), max_tokens=24,
                       ignore_eos=True)
        held = None
        for _ in range(200):
            if not core.step():
                break
            fin = [o for o in outs if o.finish_reason is not None]
            if fin and held is None:
                # the turn that told the client: the successor, already
                # issued with this row in it, still owns slot and blocks
                req = next((r for r in core.slots
                            if r is not None and r.request_id == "a"), None)
                held = req is not None
                if held:
                    assert not serial
                    assert req.state is RequestState.FINISHED
                    assert req.block_ids and core._inflight is not None
                    assert core._inflight.rows[req.slot] is req
                    slot = req.slot
                    core.step()         # reads the successor back
                    assert req.block_ids == [] and core.slots[slot] is None
        run(core)
        got = stream(outs)
        assert got[0] == ref[:at + 1] and got[3] == [want]
        assert stream(other)[0] == reference_tokens(tiny, prompt(10, 6), 24)
        assert held is (not serial)
        assert core.metrics()["ahead_discards_total"] == (0 if serial else 1)
        assert quiescent(core)
        committed[serial] = set(core.block_manager._table)
    # what is announced as resident is what the serial engine announces:
    # no block holding the position past the stop
    assert committed[False] == committed[True]


def test_rows_that_end_by_length_are_left_out_of_the_successor(tiny):
    counts = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial)
        outs = [submit(core, f"r{i}", prompt(8 + i, 20 + i),
                       max_tokens=3 + 2 * i) for i in range(4)]
        run(core)
        m = core.metrics()
        assert [len(stream(o)[0]) for o in outs] == [3, 5, 7, 9]
        assert m["ahead_discards_total"] == 0
        counts[serial] = m["decode_rows_dispatched_total"]
    # no wasted row: a row known to end is in no dispatch it cannot use
    assert counts[False] == counts[True]
    # max_model_len ends a row the same way
    core = make_core(tiny, max_model_len=32)
    outs = submit(core, "long", prompt(20, 3), max_tokens=100)
    run(core)
    assert len(stream(outs)[0]) == 12
    assert stream(outs)[3] == [FinishReason.LENGTH]
    assert core.metrics()["ahead_discards_total"] == 0


# ------------------------- (d) abort and disconnect with a dispatch in flight
def test_abort_while_a_dispatch_is_in_flight(tiny):
    toks = prompt(12, 9)
    ref = reference_tokens(tiny, toks, 30)
    core = make_core(tiny)
    outs = submit(core, "a", toks, max_tokens=30)
    keep = submit(core, "k", prompt(9, 10), max_tokens=12)
    while not (core._inflight is not None and len(stream(outs)[0]) >= 3):
        assert core.step()
    drains = core.metrics()["pipeline_drains_total"]
    core.abort("a")
    core.step()
    # an abort is handled by a quiescent engine: read back first
    assert core.metrics()["pipeline_drains_total"] == drains + 1
    run(core)
    toks_a, _, _, fin = stream(outs)
    assert fin == [FinishReason.CANCELLED]
    assert toks_a == ref[:len(toks_a)] and 3 <= len(toks_a) < 30
    assert len(stream(keep)[0]) == 12
    assert quiescent(core)


def test_a_client_that_disconnects_with_a_dispatch_in_flight(tiny):
    async def generate(engine, toks, n, leave_after=None):
        agen = engine.generate(Context(BackendInput(
            token_ids=list(toks), sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=n))))
        got = []
        async for out in agen:
            got.extend(out.token_ids)
            if out.finished or (leave_after and len(got) >= leave_after):
                break
        await agen.aclose()             # the consumer goes: the core aborts
        return got

    async def go():
        engine = AsyncLLMEngine(make_core(tiny)).start()
        try:
            stay = asyncio.ensure_future(generate(engine, prompt(9, 10), 40))
            left = await generate(engine, prompt(12, 9), 100, leave_after=3)
            stayed = await stay
            for _ in range(400):
                if not engine.core.has_work():
                    break
                await asyncio.sleep(0.01)
            return left, stayed, engine.core
        finally:
            engine.shutdown()

    left, stayed, core = asyncio.run(go())
    assert left == reference_tokens(tiny, prompt(12, 9), 3)
    assert stayed == reference_tokens(tiny, prompt(9, 10), 40)
    assert quiescent(core) and not core.has_work()
    assert core.metrics()["ahead_dispatches_total"] >= 10


# --------------------------- (e) block space runs out with one in flight
def test_block_space_running_out_with_a_dispatch_in_flight(tiny):
    got = {}
    for serial in (True, False):
        # 8 blocks of 8: two rows that each want 7 cannot both be served
        core = make_core(tiny, serial=serial, num_blocks=8)
        outs = [submit(core, "a", prompt(10, 1), max_tokens=40),
                submit(core, "b", prompt(12, 2), max_tokens=40)]
        run(core)
        got[serial] = [stream(o) for o in outs]
        m = core.metrics()
        assert m["requests_cut_short_total"] == 1
        assert m["ahead_discards_total"] == 0
        assert quiescent(core)
    assert got[False] == got[True]
    lens = sorted(len(s[0]) for s in got[False])
    assert lens[1] == 40 and lens[0] < 40
    for s, (n, seed) in zip(got[False], [(10, 1), (12, 2)]):
        assert s[3] == [FinishReason.LENGTH]
        assert s[0] == reference_tokens(tiny, prompt(n, seed), 40)[:len(s[0])]


# -------------- (f) grammar and penalty rows take the serial step, and back
def json_grammar(vocab_size):
    toks = [None] * vocab_size
    for b in range(min(256, vocab_size - 3)):
        toks[3 + b] = bytes([b])
    return JsonGrammar.from_token_bytes(toks, eos_ids=[EOS])


@pytest.mark.parametrize("special", ["penalty", "grammar"])
def test_a_grammar_or_penalty_row_falls_back_and_back_again(tiny, special):
    sampling = (SamplingOptions(temperature=0.0, frequency_penalty=0.7,
                                presence_penalty=0.3)
                if special == "penalty" else
                SamplingOptions(temperature=1.0, seed=5, json_mode=True))
    got = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, eos=[EOS],
                         grammar=json_grammar(tiny[0].config.vocab_size),
                         prefill_chunk_tokens=16)
        plain = submit(core, "plain", prompt(9, 1), max_tokens=40,
                       ignore_eos=True)
        for _ in range(4):
            core.step()                 # the plain row decodes ahead
        assert serial or core.metrics()["ahead_dispatches_total"] >= 2
        odd = submit(core, "odd", prompt(40, 2), sampling, max_tokens=10)
        together = 0
        for _ in range(400):
            ahead = core.metrics()["ahead_dispatches_total"]
            assert core.step()
            states = {r.request_id: r.state for r in core.slots if r}
            if "odd" not in states and stream(odd)[3]:
                break
            fl = core._inflight
            # its next operands need the host's token: never in flight
            assert fl is None or all(
                r.request_id != "odd" or r.state is RequestState.PREFILL
                for r in fl.rows.values())
            if (states.get("odd") is RequestState.RUNNING and fl is None):
                together += "plain" in states
                assert core.metrics()["ahead_dispatches_total"] == ahead
        assert together >= 3
        before = core.metrics()["ahead_dispatches_total"]
        run(core)
        # and back again: alone, the plain row chains as before
        assert serial or core.metrics()["ahead_dispatches_total"] > before + 5
        got[serial] = (stream(plain), stream(odd))
        assert core.metrics()["ahead_discards_total"] == 0
        assert quiescent(core)
    assert got[False] == got[True]
    assert len(got[True][0][0]) == 40


# ----------------- rule 1: nothing is queued ahead of a ready prefill
def test_the_dispatch_behind_a_decode_in_flight_is_the_ready_prefill(tiny):
    core = make_core(tiny, prefill_chunk_tokens=16)
    a = submit(core, "a", prompt(9, 1), max_tokens=40)
    for _ in range(4):
        core.step()
    assert core._inflight is not None and core._inflight.kind == "decode_multi"
    b = submit(core, "b", prompt(40, 2), max_tokens=5)   # chunks 16, 16, 8
    before = core.metrics()
    kinds, first_token_step = [], None
    for i in range(7):
        core.step()
        kinds.append(core._inflight.kind if core._inflight else None)
        if first_token_step is None and stream(b)[0]:
            first_token_step = i
    # P behind the decode in flight — never a second decode first — then
    # the alternation of the serial engine, every turn issued behind the
    # last one; the final chunk's token is read right behind the next decode
    assert kinds == ["step", "decode_multi", "step", "decode_multi", "step",
                     "decode_multi", "decode_multi"]
    assert first_token_step == 5
    m = core.metrics()
    assert m["prefill_dispatches_total"] - before["prefill_dispatches_total"] == 3
    assert m["decode_dispatches_total"] - before["decode_dispatches_total"] == 4
    assert m["ahead_dispatches_total"] - before["ahead_dispatches_total"] == 4
    assert m["pipeline_drains_total"] == 0
    run(core)
    assert len(stream(a)[0]) == 40 and len(stream(b)[0]) == 5
    assert quiescent(core)


def test_a_prefill_with_nothing_decoding_is_read_back_in_its_turn(tiny):
    core = make_core(tiny, prefill_chunk_tokens=16)
    outs = submit(core, "p", prompt(40, 2), max_tokens=2)
    for chunk in range(3):
        assert core.step() and core._inflight is None
    assert len(stream(outs)[0]) == 1    # the first token waited for nothing
    assert core.metrics()["pipeline_drains_total"] == 0
    run(core)
    assert stream(outs)[0] == reference_tokens(tiny, prompt(40, 2), 2)


# ------------------------------ rule 3 and what must never stay un-emitted
def in_flight_engine(tiny):
    core = make_core(tiny)
    outs = [submit(core, f"r{i}", prompt(8 + i, i), max_tokens=30)
            for i in range(3)]
    while core._inflight is None or core._inflight.kind != "decode_multi":
        assert core.step()
    return core, outs


def test_an_idle_engine_has_emitted_everything(tiny):
    core = make_core(tiny)
    assert core.step() is False and not core.has_work()
    outs = submit(core, "a", prompt(8, 0), max_tokens=5)
    steps = 0
    while core.step():
        steps += 1
    assert len(stream(outs)[0]) == 5 and stream(outs)[3] == [FinishReason.LENGTH]
    assert not core.has_work() and quiescent(core)
    assert steps <= 6                   # prefill + 4 decodes (+ a drain)
    assert core.step() is False


def test_fail_all_finishes_every_request_once(tiny):
    core, outs = in_flight_engine(tiny)
    assert core.has_work()
    core.fail_all()
    for o in outs:
        assert stream(o)[3] == [FinishReason.ERROR]
    assert quiescent(core) and not core.has_work()


def test_fail_all_does_not_finish_an_ended_request_again(tiny):
    toks = prompt(13, 5)
    ref = reference_tokens(tiny, toks, 24)
    at = next(i for i in range(3, len(ref)) if ref[i] not in ref[:i])
    core = make_core(tiny)
    outs = submit(core, "a", toks, max_tokens=24, stop_token_ids=[ref[at]])
    while not stream(outs)[3]:
        assert core.step()
    assert core._inflight is not None and core._inflight.ended
    core.fail_all()
    assert stream(outs)[3] == [FinishReason.STOP]
    assert quiescent(core)


def test_close_finishes_the_dispatch_in_flight(tiny):
    core, outs = in_flight_engine(tiny)
    n = [len(stream(o)[0]) for o in outs]
    core.close()
    assert core._inflight is None
    assert [len(stream(o)[0]) for o in outs] == [k + 1 for k in n]


def test_an_operation_of_another_thread_sees_a_quiescent_engine(tiny):
    core, outs = in_flight_engine(tiny)
    seen = core.run_on_step(lambda: core._inflight)
    assert core.step()
    assert seen.result(timeout=1) is None
    assert core.metrics()["pipeline_drains_total"] == 1
    run(core)
    assert [len(stream(o)[0]) for o in outs] == [30, 30, 30]


# ------------------------------------ the counters, where an operator looks
def test_the_counters_are_on_metrics_and_on_the_http_render(tiny):
    engine_counters.reset()
    core = make_core(tiny)
    submit(core, "a", prompt(8, 0), max_tokens=9, stop_token_ids=[])
    run(core)
    m = core.metrics()
    assert m["ahead_dispatches_total"] == 7 == m["decode_dispatches_total"] - 1
    # one drain: the last decode had no successor to be read behind
    assert m["ahead_discards_total"] == 0 and m["pipeline_drains_total"] == 1
    text = Metrics().render()
    for name, value in [("ahead_dispatches_total", 7),
                        ("ahead_discards_total", 0),
                        ("pipeline_drains_total", 1)]:
        assert f"dynamo_tpu_engine_{name} {value}\n" in text + "\n"


# --------------- (g) one executable, carry or no carry, on a mesh as on one
@pytest.mark.parametrize("tp", [1, 4])
def test_decode_compiles_once_with_and_without_a_carry(tiny, tp):
    mesh = None
    if tp > 1:
        from dynamo_tpu.utils.mesh import build_mesh

        if len(jax.devices()) < tp:
            pytest.skip("needs the virtual multi-device CPU mesh")
        mesh = build_mesh((1, tp), devices=jax.devices()[:tp])
    streams = {}
    for serial in (True, False):
        core = make_core(tiny, serial=serial, mesh=mesh)
        outs = submit(core, "a", prompt(8, 0), max_tokens=6)
        run(core)                       # no carry, then carries
        late = submit(core, "b", prompt(9, 1), max_tokens=6)
        run(core)
        streams[serial] = (stream(outs)[0], stream(late)[0])
        assert len(streams[serial][0]) == len(streams[serial][1]) == 6
        assert core.metrics()["ahead_dispatches_total"] == (0 if serial else 8)
        # the carried operand and the empty one are one signature (rule 4:
        # the program the warm-up builds is the one the window runs)
        assert core._multi_fn._cache_size() == 1
    assert streams[False] == streams[True]
