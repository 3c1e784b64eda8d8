"""PR 58: did the device wait?  Two writers on one timeline, and real
engines: every new key on ``snapshot()`` and on ``/metrics``, and the launch's
bracket on the profiler's events with ``benchmarks/device_wait_check.py`` over
them (``test_device_wait.py`` holds the helpers)."""

import random
import sys
import threading
from pathlib import Path

import pytest

from dynamo_tpu.obs.timeline import CLASS_KEYS, CLASSES, StepTimeline
from test_device_wait import WAIT_KEYS, Clock, turn


def hammer(work, threads=2):
    """Run ``work(i)`` on ``threads`` threads that the interpreter switches
    between every few bytecodes; returns what they raised."""
    raised = []

    def run(i):
        try:
            work(i)
        except BaseException as e:   # noqa: BLE001 - the test reports it
            raised.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=run, args=(i,)) for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sys.setswitchinterval(interval)
    return raised


def test_two_writers_on_one_timeline_never_raise():
    """Two engines in one process share the process's timeline (a colocated
    prefill and decode worker).  Their steps interleave and the numbers are
    then of neither, but no interleaving of the device's state — the flight,
    ``t_done``, the step's start — may raise into a step loop."""
    import time

    tl = StepTimeline()
    rngs = [random.Random(i) for i in range(2)]

    def work(i):
        rng, clock = rngs[i], Clock()   # ``turn`` moves a clock of its own
        before = None
        for _ in range(1500):
            done_at = time.perf_counter() + rng.random() * 2e-4
            kind = rng.choice(["step", "decode_multi", "unified", None])
            turn(tl, clock, kind,
                 rng.choice([None, lambda: time.perf_counter() >= done_at]),
                 read=[(before, 0.0)] if before and rng.random() < 0.8 else ())
            before = kind
            if rng.random() < 0.02:
                tl.in_flight(None)      # fail_all on one of the two

    assert hammer(work) == []
    snap = tl.snapshot()
    assert 0 < snap["starved_launches_total"] <= snap["launches_total"] <= 3000
    assert len(tl._flight) <= 2


def test_two_engines_in_one_process_share_the_timeline_and_finish():
    import jax

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.obs.timeline import step_timeline
    from test_request_stages import make_core, run_dry, submit

    model = LlamaModel(ModelConfig.tiny())
    params = model.init_params(jax.random.PRNGKey(0))
    cores = [make_core(model, params) for _ in range(2)]
    outs = []
    for i, core in enumerate(cores):    # compile on one thread, then race
        submit(core, f"warm{i}", 12, 3, seed=i)
        run_dry(core)
        outs.append([submit(core, f"r{i}.{j}", 12 + 8 * j, 8, seed=j)[1]
                     for j in range(3)])
    step_timeline.reset()
    assert hammer(lambda i: run_dry(cores[i])) == []
    for per_core in outs:
        assert all(o and o[-1].finish_reason is not None for o in per_core)
    snap = step_timeline.snapshot()
    assert snap["launches_total"] >= 16
    assert 0.0 <= snap["device_wait_lo_seconds_total"]
    for core in cores:
        core.close()


def test_an_engine_run_puts_every_new_key_on_the_snapshot_and_on_metrics():
    import jax

    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.obs.metric_names import SCHEMA, EngineMetric as EM
    from dynamo_tpu.obs.timeline import step_timeline
    from test_request_stages import make_core, run_dry, submit

    model = LlamaModel(ModelConfig.tiny())
    core = make_core(model, model.init_params(jax.random.PRNGKey(0)))
    step_timeline.reset()
    submit(core, "a", 40, 6, seed=1)
    submit(core, "b", 12, 6, seed=2)
    run_dry(core)
    snap = step_timeline.snapshot()
    new = list(WAIT_KEYS) + [f"{c}_{k}" for c in CLASSES for k in CLASS_KEYS]
    assert all(isinstance(snap[k], (int, float)) for k in new)
    assert not any("ewma" in k for k in snap)       # the two gauges went
    launches = sum(k["count"] for k in snap["dispatch_kinds"].values())
    assert snap["launches_total"] == launches >= 8
    assert 1 <= snap["starved_launches_total"] <= launches
    assert 0.0 <= snap["device_wait_lo_seconds_total"] \
        <= snap["device_wait_hi_seconds_total"] <= snap["wall_seconds_total"]
    assert sum(snap[f"{c}_ready_readbacks_total"] for c in CLASSES) <= launches
    assert 0.0 < snap["decode_upload_seconds_total"] \
        < snap["decode_launch_seconds_total"]
    assert step_timeline._flight == ()      # a quiet engine has read it all
    text = Metrics().render()
    for name in (EM.LAUNCHES_TOTAL, EM.STARVED_LAUNCHES_TOTAL,
                 EM.DEVICE_WAIT_SECONDS_TOTAL,
                 EM.STEP_CLASS_UPLOAD_SECONDS_TOTAL,
                 EM.STEP_CLASS_READY_READBACKS_TOTAL):
        assert f"# TYPE {name} counter" in text and name in SCHEMA
    assert f'{EM.DEVICE_WAIT_SECONDS_TOTAL}{{bound="lo"}} ' in text
    assert f'{EM.DEVICE_WAIT_SECONDS_TOTAL}{{bound="hi"}} ' in text
    assert f'{EM.STEP_CLASS_READY_READBACKS_TOTAL}{{class="decode"}} ' in text
    assert f"{EM.LAUNCHES_TOTAL} {launches}" in text
    assert "ewma" not in text
    core.close()


def test_under_a_profiler_the_event_after_a_launch_carries_its_bracket(tmp_path):
    """``dev_wait_lo_us`` / ``dev_wait_hi_us`` ride on the ``dyn.*`` event of
    the phase that opens right after a ``dispatch`` phase closed (0 for a
    launch that was not starved), on no other event, under no new name."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.obs.timeline import PHASES, step_timeline
    from test_request_stages import make_core, run_dry, submit

    model = LlamaModel(ModelConfig.tiny())
    core = make_core(model, model.init_params(jax.random.PRNGKey(0)))
    submit(core, "warm", 12, 3)
    run_dry(core)                           # compile outside the capture
    step_timeline.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        submit(core, "traced", 12, 6, seed=1)
        run_dry(core)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        tmp_path, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("dyn.")]
    events.sort(key=lambda e: e.start_ns)
    assert {e.name for e in events} <= {f"dyn.{p}" for p in PHASES}
    snap = step_timeline.snapshot()
    lo = hi = 0.0
    carriers = 0
    for before, e in zip([None] + events, events):
        stats = dict(e.stats)
        if before is not None and before.name == "dyn.dispatch":
            carriers += 1
            assert 0.0 <= float(stats["dev_wait_lo_us"]) \
                <= float(stats["dev_wait_hi_us"])
            lo += float(stats["dev_wait_lo_us"])
            hi += float(stats["dev_wait_hi_us"])
        else:
            assert "dev_wait_lo_us" not in stats, e.name
    assert carriers == snap["launches_total"]
    assert lo == pytest.approx(snap["device_wait_lo_seconds_total"] * 1e6,
                               abs=0.1 * carriers)
    assert hi == pytest.approx(snap["device_wait_hi_seconds_total"] * 1e6,
                               abs=0.1 * carriers)
    core.close()
    # benchmarks/device_wait_check.py reads the same launches off the file,
    # and lays a device's programs over their brackets: here programs made
    # up to start 50 us after each jitted call returned and to end 20 us
    # before the next one begins (a CPU profile has no XLA Modules line)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
    import device_wait_check as dwc

    launches, modules = dwc.load(path)
    assert len(launches) == carriers and modules == []
    assert sum(ln["lo_us"] for ln in launches) == pytest.approx(lo)
    modules = [[dwc.MODULE_OF[ln["kind"]] + "(7)", ln["t1"] + 50e3,
                nxt["t0"] - 20e3 - ln["t1"] - 50e3]
               for ln, nxt in zip(launches, launches[1:])]
    assert all(m[2] > 0 for m in modules)
    for ln in launches:         # every launch starved: the call, and 70 us
        ln["lo_us"] = 60.0
        ln["hi_us"] = (ln["t1"] - ln["t0"]) / 1e3 + 30.0
    out = dwc.check(launches, modules)
    assert out["summary"]["launches"] == len(launches) - 2
    assert out["summary"]["starved"] == len(launches) - 2
    assert out["summary"]["latency_us"]["median"] == pytest.approx(50.0)
    assert all(r["gap_us"] == pytest.approx(
        (r["t1"] - r["t0"]) / 1e3 + 70.0) for r in out["rows"])
    assert out["summary"]["inside_pct"] == 100.0        # hi + the latency
    assert out["summary"]["inside_hi_only_pct"] == 0.0
    assert out["summary"]["shift"] == out["summary"]["misfits"] == 0
    # a program issued before the slice's first launch shifts the pairing
    earlier = [["jit__multi_impl(7)", launches[0]["t0"] - 5e6, 1e6]]
    shifted = dwc.check(launches, earlier + modules)
    assert shifted["summary"]["shift"] == 1
    assert shifted["summary"]["launches"] == len(launches) - 1
    assert [r["gap_us"] for r in shifted["rows"][1:]] \
        == [r["gap_us"] for r in out["rows"]]
    launches[3]["lo_us"] = 5000.0           # a bracket that misses by > 1 ms
    launches[3]["hi_us"] = 6000.0
    out = dwc.check(launches, modules)
    assert [r["step"] for r in out["summary"]["far"]] == [launches[3]["step"]]
    assert out["summary"]["inside_pct"] < 100.0
