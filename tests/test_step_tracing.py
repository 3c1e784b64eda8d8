"""PR 25: the step timeline's enter-based accounting, its step classes, the
``dyn.*`` spans it writes into a ``jax.profiler`` trace, the counters the
engine keeps where the work happens, named scopes and stable module names on
the device side, and ``POST /debug/profile``."""

import asyncio
import glob
import os
import re

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore, operands
from dynamo_tpu.engine import counters as engine_counters
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import (FinishReason, SamplingOptions,
                                      StopConditions)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs.timeline import (CLASSES, KIND_CLASS, PHASES,
                                     StepTimeline, step_timeline)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class MarkTimeline:
    """The accounting as it was before PR 25: ``mark(phase)`` names the
    interval that just ended, ``end`` books the residue to host_post."""

    def __init__(self, clock):
        self.clock = clock
        self.wall = self.gap = 0.0
        self.busy = 0
        self.phases = {p: 0.0 for p in PHASES}

    def begin(self):
        self.t0 = self.last = self.clock()
        self.cur = {}

    def mark(self, phase):
        now = self.clock()
        self.cur[phase] = self.cur.get(phase, 0.0) + now - self.last
        self.last = now

    def end(self):
        self.mark("host_post")
        wall = self.clock() - self.t0
        if not any(self.cur.get(p) for p in ("upload", "dispatch", "readback")):
            return
        self.busy += 1
        self.wall += wall
        self.gap += wall - sum(self.cur.get(p, 0.0) for p in
                               ("dispatch", "readback"))
        for p, v in self.cur.items():
            self.phases[p] += v


# one decode step and one idle poll, as (phase, seconds it lasts)
STEP = [("kv_spill_restore", 0.0001), ("host_ops", 0.0002),
        ("admission", 0.0003), ("host_build", 0.002),
        ("kv_spill_restore", 0.0004), ("host_build", 0.001),
        ("upload", 0.0007), ("dispatch", 0.0005), ("readback", 0.021),
        ("host_post", 0.0015)]
IDLE = [("kv_spill_restore", 0.0001), ("host_ops", 0.0001),
        ("admission", 0.0001), ("host_build", 0.0002)]


def play(tl, clock, script, kind="decode_multi"):
    tl.begin(script[0][0])
    for i, (phase, seconds) in enumerate(script):
        if i:
            tl.enter(phase, kind=kind if phase == "dispatch" else None)
        clock.t += seconds
    tl.end()


def test_old_and_new_accounting_agree_on_a_scripted_sequence():
    c_old, c_new = Clock(), Clock()
    old, new = MarkTimeline(c_old), StepTimeline(clock=c_new)
    for script in (STEP, IDLE, STEP):
        old.begin()
        for phase, seconds in script:
            c_old.t += seconds
            old.mark(phase)
        old.end()
        play(new, c_new, script)
    snap = new.snapshot()
    assert snap["busy_steps_total"] == old.busy == 2
    assert snap["steps_total"] == 3
    assert snap["wall_seconds_total"] == pytest.approx(old.wall, rel=1e-12)
    assert new.host_gap_s_total == pytest.approx(old.gap, rel=1e-12)
    assert snap["host_gap_ms_per_turn"] == pytest.approx(
        old.gap / old.busy * 1e3, rel=1e-12)
    for p in PHASES:
        assert snap["phases"][p] == pytest.approx(old.phases[p], abs=1e-15), p
    assert sum(snap["phases"].values()) == pytest.approx(
        snap["wall_seconds_total"], rel=1e-12)


@pytest.mark.parametrize("kind", sorted(KIND_CLASS))
def test_readback_lands_on_the_preceding_dispatch_kind(kind):
    clock = Clock()
    tl = StepTimeline(clock=clock)
    play(tl, clock, STEP, kind=kind)
    snap = tl.snapshot()
    facing = 0.0005 + 0.021      # dispatch + readback
    assert snap["dispatch_kinds"] == {
        kind: {"seconds": pytest.approx(facing), "count": 1}}
    cls = KIND_CLASS[kind]
    assert snap[f"{cls}_steps_total"] == 1
    assert snap[f"{cls}_device_seconds_total"] == pytest.approx(facing)
    assert snap[f"{cls}_wall_seconds_total"] == pytest.approx(
        snap["wall_seconds_total"])
    assert snap["wall_seconds_total"] - facing == pytest.approx(
        tl.host_gap_s_total)


def test_class_totals_add_up_to_wall():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    for kind in ("step", "decode_multi", "decode_multi", "unified",
                 "prefill_ragged", None):
        play(tl, clock, STEP, kind=kind)
    # two classes in one step: a prefill and a decode dispatch -> mixed
    tl.begin("host_build")
    for phase, kind in (("dispatch", "step"), ("readback", None),
                        ("dispatch", "decode_multi"), ("readback", None),
                        ("host_post", None)):
        clock.t += 0.001
        tl.enter(phase, kind=kind)
    clock.t += 0.001
    tl.end()
    play(tl, clock, IDLE)
    snap = tl.snapshot()
    assert [snap[f"{c}_steps_total"] for c in CLASSES] == [2, 2, 3]
    assert sum(snap[f"{c}_steps_total"] for c in CLASSES) \
        == snap["busy_steps_total"] == 7
    assert sum(snap[f"{c}_wall_seconds_total"] for c in CLASSES) \
        == pytest.approx(snap["wall_seconds_total"], rel=1e-12)
    assert sum(snap[f"{c}_device_seconds_total"] for c in CLASSES) \
        == pytest.approx(snap["wall_seconds_total"] - tl.host_gap_s_total,
                         rel=1e-12)
    # the flat keys are top-level numbers: cellbench's snapshot keeps those
    assert all(isinstance(snap[f"{c}_{k}"], (int, float)) for c in CLASSES
               for k in ("steps_total", "wall_seconds_total",
                         "device_seconds_total"))


def test_a_step_that_issues_ahead_is_booked_to_what_it_issues():
    """PR 28: a step issues dispatch N+1 and then reads N back.  The
    readback goes to N's kind and counts no dispatch; the step's class is
    the one it issued, or, with none issued, the one it finished."""
    clock = Clock()
    tl = StepTimeline(clock=clock)

    def step(script):
        tl.begin("host_build")
        for phase, kw in script:
            clock.t += 0.001
            tl.enter(phase, **kw)
        clock.t += 0.001
        tl.end()

    ahead = [("upload", {}), ("dispatch", dict(kind="decode_multi")),
             ("readback", dict(kind="step", issued=False)), ("host_post", {})]
    step(ahead)                         # a decode behind a prefill chunk
    step([("upload", {}), ("dispatch", dict(kind="step"))])   # stays un-read
    step([("readback", dict(kind="step", issued=False)),
          ("host_post", {})])           # nothing to issue: only finishes
    snap = tl.snapshot()
    assert {k: v["count"] for k, v in snap["dispatch_kinds"].items()} \
        == {"decode_multi": 1, "step": 1}
    assert snap["dispatch_kinds"]["step"]["seconds"] == pytest.approx(0.003)
    assert snap["dispatch_kinds"]["decode_multi"]["seconds"] \
        == pytest.approx(0.001)
    assert [snap[f"{c}_steps_total"] for c in CLASSES] == [2, 1, 0]
    assert snap["busy_steps_total"] == 3
    assert sum(snap["phases"].values()) == pytest.approx(
        snap["wall_seconds_total"], rel=1e-12)
    assert sum(snap[f"{c}_wall_seconds_total"] for c in CLASSES) \
        == pytest.approx(snap["wall_seconds_total"], rel=1e-12)


def test_without_a_profiler_session_a_phase_makes_no_span():
    clock = Clock()
    tl = StepTimeline(clock=clock)
    tl.begin()
    assert tl._span is None
    tl.enter("dispatch", kind="step")
    assert tl._span is None
    tl.end()
    assert not hasattr(tl, "recent")


# ------------------------------------------------------------- a real engine
@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(model, params, **kw):
    cfg = dict(max_batch_size=4, max_model_len=128, block_size=8,
               num_blocks=64, prefill_buckets=[16, 32, 64, 128])
    cfg.update(kw)
    return EngineCore(model, params, EngineConfig(**cfg))


def submit(core, rid, prompt_len, max_tokens, seed=0):
    outs = []
    prompt = np.random.RandomState(seed).randint(1, 200, size=prompt_len)
    core.submit(EngineRequest(
        rid, [int(t) for t in prompt], SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=max_tokens), outs.append))
    return outs


def run_dry(core, limit=400):
    for _ in range(limit):
        if not core.step():
            return
    raise AssertionError("the engine did not drain")


def test_profiled_engine_run_yields_dyn_events_on_one_host_line(tiny, tmp_path):
    """A tiny engine under jax.profiler: every phase is a ``dyn.<phase>``
    event with step, kind and t_mono_ns, all on the engine's own thread."""
    from jax.profiler import ProfileData

    core = make_core(*tiny)
    outs = submit(core, "warm", 12, 3)
    run_dry(core)                           # compile outside the capture
    step_timeline.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        outs = submit(core, "traced", 12, 4, seed=1)
        run_dry(core)
    finally:
        jax.profiler.stop_trace()
    assert sum(len(o.token_ids) for o in outs) == 4
    path = glob.glob(os.path.join(
        tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [e for e in line.events if e.name.startswith("dyn.")]
            if events:
                lines.append(events)
    assert len(lines) == 1                  # one thread: the stepping one
    events = lines[0]
    names = {e.name for e in events}
    assert {"dyn.host_build", "dyn.upload", "dyn.dispatch", "dyn.readback",
            "dyn.host_post"} <= names <= {f"dyn.{p}" for p in PHASES}
    kinds = set()
    for e in events:
        stats = dict(e.stats)
        assert int(stats["t_mono_ns"]) > 0 and int(stats["step"]) >= 0
        if e.name in ("dyn.dispatch", "dyn.readback"):
            kinds.add(stats["kind"])
        else:
            assert not stats.get("kind")
    assert kinds == {"step", "decode_multi"}
    # leaves, one open at a time: no event overlaps the next
    events.sort(key=lambda e: e.start_ns)
    for a, b in zip(events, events[1:]):
        assert a.start_ns + a.duration_ns <= b.start_ns + 1000
    # the busy-step index on the spans is the timeline's own; every
    # busy step issues a dispatch or reads one back, and every dispatch is
    # read back once (in its own step, or in the step that issued the next)
    steps = {int(dict(e.stats)["step"]) for e in events
             if e.name in ("dyn.dispatch", "dyn.readback")}
    assert steps == set(range(step_timeline.busy_steps_total))
    assert sum(e.name == "dyn.dispatch" for e in events) \
        == sum(e.name == "dyn.readback" for e in events)
    snap = step_timeline.snapshot()
    assert snap["prefill_steps_total"] == 1 and snap["decode_steps_total"] >= 3
    assert snap["mixed_steps_total"] == 0


def test_profiled_dispatch_events_say_what_the_dispatch_carried(tiny, tmp_path):
    """PR 43: ``dyn.upload`` / ``dyn.dispatch`` / ``dyn.readback`` carry
    ``rows``, ``tokens`` (prompt tokens of a prefill dispatch, rows of a
    decode) and ``ctx`` (the sum of the rows' context lengths) beside
    ``step``, ``kind`` and ``t_mono_ns``; the readback of a dispatch says
    what *that* dispatch carried, whichever step reads it back; no other
    phase carries them, and with no session open nothing is built."""
    from jax.profiler import ProfileData

    core = make_core(*tiny, prefill_chunk_tokens=16)
    submit(core, "warm-a", 20, 3)
    submit(core, "warm-b", 12, 3, seed=3)
    run_dry(core)                           # compile outside the capture
    assert core._carried(1, 1, np.asarray([5])) == {}     # no session open
    assert core._inflight is None
    step_timeline.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert core._carried(2, 2, np.asarray([5, 6])) == {
            "rows": 2, "tokens": 2, "ctx": 11}
        submit(core, "a", 20, 5, seed=1)    # chunks of 16 and 4
        submit(core, "b", 12, 5, seed=2)
        run_dry(core)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("dyn.")]
    events.sort(key=lambda e: e.start_ns)
    seen = {"dyn.upload": [], "dyn.dispatch": [], "dyn.readback": []}
    for e in events:
        stats = dict(e.stats)
        if e.name in seen:
            seen[e.name].append(
                (stats.get("kind", ""),) + tuple(
                    int(stats[k]) for k in ("rows", "tokens", "ctx")))
        else:
            assert not {"rows", "tokens", "ctx"} & set(stats), e.name
    # the prefill chunks in order of admission: a's 16 and 4, then b's 12
    prefills = [c for c in seen["dyn.dispatch"] if c[0] == "step"]
    assert prefills == [("step", 1, 16, 16), ("step", 1, 4, 20),
                        ("step", 1, 12, 12)]
    decodes = [c for c in seen["dyn.dispatch"] if c[0] == "decode_multi"]
    assert decodes and all(rows == tokens and rows in (1, 2)
                           for _, rows, tokens, _ in decodes)
    assert any(rows == 2 for _, rows, _, _ in decodes)
    # a decodes at lengths 21..24 and b at 13..16: every position once
    assert sum(ctx for _, _, _, ctx in decodes) \
        == sum(range(21, 25)) + sum(range(13, 17))
    # an upload carries what its dispatch does (kind is on the device-facing
    # phases only), and every dispatch is read back once, as itself
    assert [c[1:] for c in seen["dyn.upload"]] \
        == [c[1:] for c in seen["dyn.dispatch"]]
    assert sorted(seen["dyn.readback"]) == sorted(seen["dyn.dispatch"])


def test_counters_cut_short_is_not_max_tokens(tiny):
    """A cache made to run out: the request that loses its block space is
    counted cut short; the one that stops at max_tokens is not."""
    engine_counters.reset()
    core = make_core(*tiny, num_blocks=6, max_batch_size=2)
    # 8-token blocks: 17-token prompts take 3 blocks each, all 6; the first
    # to need a 4th block (at 24 tokens) finds none
    a = submit(core, "a", 17, 40, seed=1)
    b = submit(core, "b", 17, 3, seed=2)
    run_dry(core)
    m = core.metrics()
    assert b[-1].finish_reason == FinishReason.LENGTH
    assert sum(len(o.token_ids) for o in b) == 3          # max_tokens
    assert a[-1].finish_reason == FinishReason.LENGTH
    assert sum(len(o.token_ids) for o in a) < 40          # cut short
    assert m["requests_finished_total"] == 2
    assert m["requests_cut_short_total"] == 1
    assert m["first_tokens_total"] == 2
    assert 0 < m["first_token_seconds_total"] < 60
    assert m["decode_dispatches_total"] >= 3
    # rows per dispatch: two rows while both ran, one after b stopped
    rows = m["decode_rows_dispatched_total"] / m["decode_dispatches_total"]
    assert 1.0 < rows < 2.0
    assert all(isinstance(m[k], (int, float)) for k in m)
    # what /metrics renders: the process's engines summed, here this one
    totals = engine_counters.engine_totals()
    assert totals.requests_cut_short_total == 1
    assert totals.requests_finished_total == 2
    assert totals.decode_dispatches_total == m["decode_dispatches_total"]

    # too long for the model is LENGTH too, and is not "cut short"
    core = make_core(*tiny)
    c = submit(core, "c", 130, 4)
    run_dry(core)
    assert c[-1].finish_reason == FinishReason.LENGTH
    assert core.metrics()["requests_cut_short_total"] == 0
    assert core.metrics()["requests_finished_total"] == 1


def test_dispatch_ahead_keeps_the_accounting(tiny):
    """PR 29: with a dispatch in flight the per-phase wall time still sums
    to the step wall time, a class's steps still count its dispatches, and
    the rows and the prefill dispatches are those of the serial engine (a
    row joins the decode behind its final chunk's, so a decode dispatch
    may be added: no row is dispatched twice for one token)."""
    got = {}
    for sync in (True, False):
        core = make_core(*tiny, prefill_chunk_tokens=16)
        if sync:
            core._may_stay_in_flight = lambda rec: False
        step_timeline.reset()
        outs = [submit(core, f"r{i}", 9 + 12 * i, 5 + 4 * i, seed=i)
                for i in range(3)]
        run_dry(core)
        m, snap = core.metrics(), step_timeline.snapshot()
        assert [sum(len(o.token_ids) for o in out) for out in outs] == [5, 9, 13]
        assert sum(snap["phases"].values()) == pytest.approx(
            snap["wall_seconds_total"], rel=1e-9)
        assert sum(snap[f"{c}_wall_seconds_total"] for c in CLASSES) \
            == pytest.approx(snap["wall_seconds_total"], rel=1e-9)
        assert snap["mixed_steps_total"] == 0
        assert snap["dispatch_kinds"]["decode_multi"]["count"] \
            == m["decode_dispatches_total"]
        assert snap["dispatch_kinds"]["step"]["count"] \
            == m["prefill_dispatches_total"]
        # a step that only finishes adds one to its class, never takes one
        assert snap["decode_steps_total"] >= m["decode_dispatches_total"]
        assert snap["prefill_steps_total"] == m["prefill_dispatches_total"]
        assert (m["ahead_dispatches_total"] > 0) is (not sync)
        got[sync] = (m["decode_rows_dispatched_total"],
                     m["prefill_dispatches_total"])
    assert got[False] == got[True]


MODULE_NAMES = {"step": ("_step_fn", "jit__step_impl"),
                "decode_multi": ("_multi_fn", "jit__multi_impl"),
                "spec_verify": ("_spec_fn", "jit__spec_impl"),
                "prefill_ragged": ("_ragged_fn", "jit__ragged_impl"),
                "unified": ("_unified_fn", "jit__unified_impl")}


@pytest.mark.parametrize("kind", sorted(MODULE_NAMES))
def test_dispatch_kind_keeps_its_module_name(tiny, kind):
    """cellbench's device.*_program_ms find a program on the profile's
    ``XLA Modules`` line by this name (``jit_`` + the impl's name)."""
    attr, module = MODULE_NAMES[kind]
    fn = getattr(make_core(*tiny), attr)
    assert "jit_" + fn.__wrapped__.__name__ == module
    assert kind in KIND_CLASS


def test_model_scopes_reach_the_compiled_program(tiny):
    """The named scopes sit on the operations' locations, which become the
    profile's ``tf_op``."""
    model, params = tiny
    core = make_core(model, params)
    b, m = 4, core.config.max_blocks_per_seq
    i32 = lambda *s: np.zeros(s, np.int32)
    f32 = lambda *s: np.zeros(s, np.float32)
    # a decode's operands as the engine sends them: one buffer (its first
    # word: which key of the block), None where the impl takes its key
    bufs, layout = operands.pack((
        i32(), (i32(b), i32(b), i32(b, m), i32(b), i32(b), None, f32(b),
                i32(b), f32(b)), {}))
    text = core._multi_fn.lower(
        core.params, core.cache, core._keys, bufs, layout=layout,
    ).as_text(debug_info=True)
    # a part of an operation's name-stack path: behind ``jit(_multi_impl)/``
    # on the step's own operations, leading it inside the layer scan's body
    on_a_path = lambda scope: re.search(
        rf'loc\("(?:[^"]*/)?{scope}[/"]', text)
    for scope in ("embed", "attn_proj", "attn", "attn_out", "mlp", "logits",
                  "sample"):
        assert on_a_path(scope), scope
    assert not on_a_path("moe_router")   # a dense model has no router


# -------------------------------------------------------- POST /debug/profile
def test_debug_profile_route(tmp_path):
    from aiohttp import ClientSession

    from dynamo_tpu.llm.http import HttpService

    async def go():
        off = HttpService(port=0)
        on = HttpService(port=0, profile_dir=str(tmp_path))
        await off.start()
        await on.start()
        try:
            async with ClientSession() as s:
                r = await s.post(
                    f"http://127.0.0.1:{off.port}/debug/profile?seconds=0.1")
                assert r.status == 409
                assert "--profile-dir" in (await r.json())["error"]
                base = f"http://127.0.0.1:{on.port}/debug/profile"
                for bad in ("0", "-1", "61", "soon"):
                    r = await s.post(f"{base}?seconds={bad}")
                    assert r.status == 400, bad
                r = await s.post(f"{base}?seconds=0.2")
                assert r.status == 200
                body = await r.json()
                assert body["path"].startswith(str(tmp_path))
                assert glob.glob(os.path.join(
                    body["path"], "plugins", "profile", "*", "*.xplane.pb"))
        finally:
            await off.stop()
            await on.stop()

    asyncio.run(go())
