"""The readers PR 25 added, on two recordings from the chip and on counters:

``data/explore.xplane.pb`` — a whole small profile (a jitted toy model with the
model's scope names and a layer scan, five calls under ``dyn.dispatch`` /
``dyn.readback`` annotations), which also proves the wire-format walk;
``data/recorded_spans.json`` — a few engine steps around one prefill step of
``mistral-7b.chat-open`` as ``readers/tracefile.py`` parsed them.

Three identities are pinned: the five ``idle.*`` add up to ``device.idle_pct``,
the four ``device.*_pct`` and ``embed`` to 100, and the step classes to the
wall of all busy steps."""

import json
import os
import shutil

import pytest

from cellbench import spec, trace_reduce
from roots import HERE, REPO

TPU = spec.load_settings(REPO)["trace"]["device"]["tpu"]
NEW = ["step.decode_wall_ms", "step.prefill_wall_ms", "step.prefill_share_pct",
       "sched.decode_rows_per_dispatch", "engine.ttft_ms", "kv.cut_short_pct",
       "device.decode_program_ms", "device.prefill_program_ms",
       "idle.launch_pct", "idle.readback_pct", "idle.host_pre_pct",
       "idle.host_post_pct", "idle.between_steps_pct", "device.attn_pct",
       "device.mlp_pct", "device.head_pct", "device.unscoped_pct"]
IDLE = [n for n in NEW if n.startswith("idle.")]
SCOPE = [n for n in NEW if n.startswith("device.") and n.endswith("_pct")]


def tracefile():
    return spec.load_module(REPO, "readers", "tracefile")


def read(name, ctx):
    desc = spec.load_layer_metric(REPO, name)
    return spec.load_module(REPO, "readers", desc["reader"]).read(
        ctx, desc.get("args", {}))


@pytest.fixture(scope="module")
def explore_ctx(tmp_path_factory):
    """A run's context whose profile directory holds the small profile."""
    d = tmp_path_factory.mktemp("trace")
    where = d / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    shutil.copy(HERE / "data" / "explore.xplane.pb", where / "host.xplane.pb")
    return {"trace_dir": str(d), "root": REPO, "device": {"platform": "tpu"}}


@pytest.fixture(scope="module")
def recorded():
    return json.loads((HERE / "data" / "recorded_spans.json").read_text())["rows"]


def test_new_metrics_are_all_declared():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    i = names.index(NEW[0])
    assert names[i:i + len(NEW)] == NEW     # one run, in the issue's order
    # every cell reports them: none lists cells (later entries may)
    assert not any("workloads" in m for m in bench["per_layer"][i:i + len(NEW)])


def test_the_wire_walk_agrees_with_profiledata(explore_ctx):
    path = trace_reduce.find_xplane(explore_ctx["trace_dir"])
    t = tracefile().parse(path, TPU)
    old = trace_reduce.load(path, TPU)["devices"]
    assert list(t["devices"]) == list(old) == ["/device:TPU:0"]
    mine = sorted(t["devices"]["/device:TPU:0"]["ops"], key=lambda r: r[1])
    theirs = sorted(old["/device:TPU:0"], key=lambda r: r[1])
    assert len(mine) == len(theirs) == 105
    for a, b in zip(mine, theirs):
        assert a[0] == b[0] and a[1] == pytest.approx(b[1], abs=1.5)
        assert a[2] == pytest.approx(b[2], abs=1.5)


def test_spans_scopes_and_programs_are_read(explore_ctx):
    t = tracefile().for_run(explore_ctx)
    assert t is tracefile().for_run(explore_ctx)        # parsed once
    assert t["scoped"] is True
    assert [s[0] for s in t["spans"]] == ["dyn.dispatch", "dyn.readback"] * 5
    for i, s in enumerate(t["spans"]):
        assert s[3] == i // 2 and s[5] > 0              # step, t_mono_ns
        assert s[4] == ("decode_multi" if s[0] == "dyn.dispatch" else "")
    # t_mono_ns is one clock: the offset to trace time is one number
    offsets = [s[1] - s[5] for s in t["spans"]]
    assert max(offsets) - min(offsets) < 50_000         # ns
    dev = t["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 5
    assert {m[0].split("(")[0] for m in dev["modules"]} == {"jit__multi_impl"}
    assert {r[4] for r in dev["ops"]} == {"jit__multi_impl"}
    scopes = {r[0]: r[3] for r in dev["ops"]}
    assert scopes["while"] == "" and scopes["fusion.26"] == "attn_proj"
    assert scopes["fusion.27"] == "mlp"                 # mlp/moe_router/...
    assert scopes["iota_reduce_fusion"] == "logits"


def test_each_new_trace_reader_on_the_small_profile(explore_ctx):
    got = {n: read(n, explore_ctx) for n in NEW if n.split(".")[0] in
           ("idle", "device")}
    assert got["device.decode_program_ms"] == pytest.approx(0.3892, abs=1e-3)
    assert got["device.prefill_program_ms"] is None     # no such program ran
    assert got["device.attn_pct"] == pytest.approx(27.05, abs=0.01)
    assert got["device.mlp_pct"] == pytest.approx(25.75, abs=0.01)
    assert got["device.head_pct"] == pytest.approx(46.53, abs=0.01)
    assert got["device.unscoped_pct"] == pytest.approx(0.04, abs=0.01)
    assert got["idle.launch_pct"] == pytest.approx(32.09, abs=0.01)
    assert got["idle.readback_pct"] == pytest.approx(37.74, abs=0.01)
    assert got["idle.host_pre_pct"] == got["idle.host_post_pct"] == 0.0
    assert got["idle.between_steps_pct"] == pytest.approx(0.76, abs=0.01)
    reduced = trace_reduce.reduce(trace_reduce.load(
        trace_reduce.find_xplane(explore_ctx["trace_dir"]), TPU))
    assert sum(got[n] for n in IDLE) == pytest.approx(reduced["idle_pct"], abs=1.0)


def test_nothing_to_read_is_none_and_never_raises(tmp_path):
    empty = {"trace_dir": str(tmp_path), "root": REPO,
             "device": {"platform": "tpu"}, "edges": ({}, {})}
    none = {"trace_dir": None, "root": REPO, "device": {"platform": "cpu"},
            "edges": ({"timeline.wall_seconds_total": 1.0},
                      {"timeline.wall_seconds_total": 2.0})}
    for ctx in (empty, none):
        assert all(read(n, ctx) is None for n in NEW)
    # a program from before PR 25: operations, but no span and no scope
    t = {"devices": {"d": {"ops": [["fusion.1", 0.0, 5e4, "", "p"],
                                   ["fusion.1", 9e4, 5e4, "", "p"]],
                           "modules": []}}, "spans": [], "scoped": False}
    assert tracefile().idle_by_span(t) is None
    assert tracefile().idle_gaps(t) == [(5e4, 9e4)]


def test_idle_by_phase_adds_up_to_the_idle_share(recorded):
    tf = tracefile()
    idle, (w0, w1) = tf.idle_by_span(recorded), tf.window(recorded)
    dev = next(iter(recorded["devices"].values()))
    reduced = trace_reduce.reduce(
        {"devices": {"d": [r[:3] for r in dev["ops"]]}, "host": []})
    share = {k: 100.0 * v / ((w1 - w0) / 1e9) for k, v in idle.items()}
    assert sum(share.values()) == pytest.approx(reduced["idle_pct"], abs=1.0)
    assert set(share) <= {"", *(f"dyn.{p}" for p in (
        "kv_spill_restore", "host_ops", "admission", "host_build", "upload",
        "dispatch", "overlap", "readback", "host_post"))}
    # what the chip showed: the device waits longest while the host uploads
    # the next step's operands
    assert max(share, key=share.get) == "dyn.upload"
    # every metric file's spans are phases the engine writes, each once
    listed = [s for n in IDLE for s in
              spec.load_layer_metric(REPO, n)["args"]["spans"]]
    assert sorted(listed) == sorted(f"dyn.{p}" for p in (
        "kv_spill_restore", "host_ops", "admission", "host_build", "upload",
        "dispatch", "overlap", "readback", "host_post"))


def test_device_time_by_scope_adds_up_to_all_of_it(recorded):
    tf = tracefile()
    by = tf.scope_seconds(recorded)
    total = sum(by.values())
    assert set(by) <= {"", *tf.SCOPES}
    listed = [s for n in SCOPE for s in
              spec.load_layer_metric(REPO, n)["args"]["scopes"]]
    assert sorted(listed + ["embed"]) == sorted(tf.SCOPES)
    four = sum(by.get(s, 0.0) for s in listed) + by.get("", 0.0)
    assert 100.0 * (four + by.get("embed", 0.0)) / total == pytest.approx(100.0, abs=2.0)
    assert by["mlp"] > by["attn"] > 0 and by[""] > 0
    dev = next(iter(recorded["devices"].values()))
    # the same busy time as by name (self_times clamps a name's total at
    # zero, so the two groupings differ by the loop's rounding, under 1%)
    assert total == pytest.approx(sum(trace_reduce.self_times(
        [r[:3] for r in dev["ops"]]).values()), rel=0.01)
    # both programs of the legacy scheduler ran, each under its own name
    programs = {m[0].split("(")[0] for m in dev["modules"]}
    assert programs == {"jit__step_impl", "jit__multi_impl",
                        "jit__threefry_split", "jit__unstack"}   # + rng split


def test_step_classes_add_up_to_the_wall_exactly():
    """(step.decode_wall_ms x decode steps + prefill + mixed) = wall, through
    the same reader and edge names the metric files give."""
    from dynamo_tpu.obs.timeline import StepTimeline

    t = [0.0]
    tl = StepTimeline(clock=lambda: t[0])

    def edge():
        return {f"timeline.{k}": v for k, v in tl.snapshot().items()
                if isinstance(v, (int, float))}

    def step(kind, ms):
        tl.begin("host_build")
        t[0] += 0.002
        tl.enter("dispatch", kind=kind)
        t[0] += ms / 1e3
        tl.enter("host_post")
        t[0] += 0.001
        tl.end()

    step("decode_multi", 20)
    before = edge()
    for kind, ms in (("decode_multi", 25), ("step", 60), ("decode_multi", 26),
                     ("unified", 40), ("decode_multi", 24)):
        step(kind, ms)
    ctx = {"edges": (before, edge()), "root": REPO}
    after = ctx["edges"][1]
    n = lambda c: after[f"timeline.{c}_steps_total"] - before[f"timeline.{c}_steps_total"]
    decode, prefill = read("step.decode_wall_ms", ctx), read("step.prefill_wall_ms", ctx)
    assert decode == pytest.approx(28.0) and prefill == pytest.approx(63.0)
    mixed = (after["timeline.mixed_wall_seconds_total"]
             - before["timeline.mixed_wall_seconds_total"]) * 1e3
    wall = (after["timeline.wall_seconds_total"]
            - before["timeline.wall_seconds_total"]) * 1e3
    assert decode * n("decode") + prefill * n("prefill") + mixed == pytest.approx(wall, rel=1e-12)
    assert read("step.prefill_share_pct", ctx) == pytest.approx(100 * 63.0 / wall)
    assert read("step.wall_ms", ctx) == pytest.approx(wall / 5)


def test_counter_metrics_read_the_engine_counters():
    before = {"core.decode_rows_dispatched_total": 100, "core.decode_dispatches_total": 10,
              "core.first_token_seconds_total": 1.0, "core.first_tokens_total": 4,
              "core.requests_cut_short_total": 0, "core.requests_finished_total": 3,
              "core.ahead_dispatches_total": 9, "core.ahead_discards_total": 2}
    after = {"core.decode_rows_dispatched_total": 424, "core.decode_dispatches_total": 37,
             "core.first_token_seconds_total": 2.5, "core.first_tokens_total": 16,
             "core.requests_cut_short_total": 1, "core.requests_finished_total": 13,
             "core.ahead_dispatches_total": 33, "core.ahead_discards_total": 11}
    ctx = {"edges": (before, after), "root": REPO}
    assert read("sched.decode_rows_per_dispatch", ctx) == pytest.approx(12.0)
    # dispatch-ahead (PR 29): 24 of 27 decode dispatches went out with one in
    # flight; a late stop threw away the ahead-sample of 9 of 324 rows
    assert read("sched.ahead_dispatch_pct", ctx) == pytest.approx(100 * 24 / 27)
    assert read("sched.ahead_discard_pct", ctx) == pytest.approx(100 * 9 / 324)
    assert read("engine.ttft_ms", ctx) == pytest.approx(125.0)
    assert read("kv.cut_short_pct", ctx) == pytest.approx(10.0)
    # a program without the counters: nothing, not an error
    assert read("kv.cut_short_pct", {"edges": ({}, {}), "root": REPO}) is None


# ------------------------------------------------- a traced rehearsal, on the CPU
@pytest.fixture(scope="module")
def traced_rehearsal(tmp_path_factory):
    import subprocess
    import sys

    import roots

    root = roots.build(tmp_path_factory.mktemp("root"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", "tiny-moe.closed",
         "--seed", str(2**31 + 29), "--seconds", "1.5", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


def test_traced_rehearsal_reports_the_counter_based_metrics(traced_rehearsal):
    p = traced_rehearsal
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("step.decode_wall_ms", "step.prefill_wall_ms",
                 "step.prefill_share_pct", "sched.decode_rows_per_dispatch",
                 "engine.ttft_ms", "kv.cut_short_pct", *IDLE):
        assert name in m, name
    assert m["kv.cut_short_pct"] == 0.0 and m["engine.ttft_ms"] > 0
    assert 1.0 <= m["sched.decode_rows_per_dispatch"] <= 8.0
    assert 0.0 < m["sched.ahead_dispatch_pct"] <= 100.0
    assert 0.0 <= m["sched.ahead_discard_pct"] < 100.0
    assert not any(n.startswith("coll.") for n in m)    # the four-chip cell's
    assert 0 < m["step.prefill_share_pct"] < 100
    assert sum(m[n] for n in IDLE) == pytest.approx(m["device.idle_pct"], abs=1.0)
    # the CPU's profile has no module line and no operation metadata
    assert not any(n in m for n in SCOPE + ["device.decode_program_ms",
                                            "device.prefill_program_ms"])
