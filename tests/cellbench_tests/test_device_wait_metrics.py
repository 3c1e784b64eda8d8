"""The seven per-layer metrics of PR 58 (did the device wait?): each has its
file, goes through the accepted reader ``counter_ratio``, names counters that
the step timeline's ``snapshot()`` holds under the dotted names
``cellbench/run.py::snapshot`` gives them, and reads the value that a
recorded pair of edges implies — and nothing, without raising, from a program
that lacks the counters (the parent of PR 58).  Six tests and no more: under
``-n 6 --dist loadfile`` a small file does not run beside a timed rehearsal."""

import json

import pytest

from cellbench import spec
from roots import REPO

DEVICE = ["device.wait_lo_pct", "device.wait_hi_pct",
          "device.starved_launch_pct"]
STEP = {"step.decode_ready_readback_pct": "itl_p95_ms",
        "step.prefill_ready_readback_pct": "ttft_mean_ms",
        "step.decode_upload_ms": "itl_p95_ms",
        "step.prefill_upload_ms": "ttft_mean_ms"}
SEVEN = DEVICE + list(STEP)


def read(name, edges):
    desc = spec.load_layer_metric(REPO, name)
    return spec.load_module(REPO, "readers", desc["reader"]).read(
        {"edges": edges, "root": REPO}, desc.get("args", {}))


@pytest.fixture(scope="module")
def edges():
    """A timeline at virtual time.  Before: one serial prefill.  After: a
    decode issued behind nothing and left in flight; a decode issued behind
    it while it runs (the host blocks 9 ms on it); a prefill turn that comes
    after the decode before it has finished and reads it back at once; a
    step with no work and 2 s of sleep; a serial prefill."""
    from dynamo_tpu.obs.timeline import StepTimeline

    t = [50.0]
    tl = StepTimeline(clock=lambda: t[0])

    def turn(kind=None, done_at=None, read=(), upload=0.001):
        tl.begin()
        t[0] += 0.002
        tl.enter("host_build")
        t[0] += 0.001
        if kind:
            tl.enter("upload")
            t[0] += upload
            tl.enter("dispatch", kind=kind)
            t[0] += 0.0005
            tl.in_flight(lambda: t[0] >= done_at)
        for rkind, blocks in read:
            tl.enter("readback", kind=rkind, issued=False)
            t[0] += blocks
            tl.enter("host_post")
            t[0] += 0.001
        tl.end()

    def edge():
        return {f"timeline.{k}": v for k, v in tl.snapshot().items()
                if isinstance(v, (int, float))}

    turn("step", t[0] + 0.02, read=[("step", 0.02)])
    before = edge()
    turn("decode_multi", t[0] + 0.012, upload=0.002)    # starved: lo 0.005
    turn("decode_multi", t[0] + 0.02, read=[("decode_multi", 0.009)],
         upload=0.002)                                  # not starved
    t[0] += 0.012                       # the second decode finished meanwhile
    turn("step", t[0] + 0.05, read=[("decode_multi", 0.0)])     # starved
    turn(read=[("step", 0.05)])         # finishes the prefill: blocked
    turn()
    t[0] += 2.0
    turn("step", t[0] + 0.02, read=[("step", 0.02)])    # starved: lo 0.004
    return before, edge()


def test_the_seven_are_declared_for_every_cell_with_what_they_move():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in SEVEN:
        m = declared[name]
        assert "workloads" not in m                     # every cell owes it
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == STEP.get(name, "tok_s_chip")
        assert m["layer"] == ("device" if name in DEVICE else "engine step")
        assert m["unit"] == ("ms" if name.endswith("_ms") else "%")


def test_each_file_loads_and_names_counters_the_edges_hold(edges):
    for name in SEVEN:
        desc = spec.load_layer_metric(REPO, name)
        assert desc["name"] == name and desc["note"]
        assert desc["reader"] == "counter_ratio"        # no reader is new
        for side in edges:
            assert desc["args"]["num"] in side and desc["args"]["den"] in side
        assert isinstance(read(name, edges), float)


def test_the_device_metrics_read_the_bracket_the_edges_imply(edges):
    before, after = edges
    d = lambda k: after[f"timeline.{k}"] - before[f"timeline.{k}"]
    assert d("launches_total") == 4 and d("starved_launches_total") == 3
    assert read("device.starved_launch_pct", edges) == pytest.approx(75.0)
    # first decode: from the serial prefill's readback (1 ms of host_post,
    # 3 ms before the upload, 2 ms of upload) to the dispatch's open / close;
    # the prefill turn: seen done at begin, 3 ms + 1 ms of upload later it
    # launches, and the device last held work for certain at the end of the
    # step before (the poll there said "not done"): the 12 ms, then 4.5 ms;
    # the last prefill: cut at its begin by the step with no work
    lo = 0.006 + 0.004 + 0.004
    hi = 0.0065 + (0.012 + 0.0045) + 0.0045
    assert d("device_wait_lo_seconds_total") == pytest.approx(lo)
    assert d("device_wait_hi_seconds_total") == pytest.approx(hi)
    wall = d("wall_seconds_total")
    assert read("device.wait_lo_pct", edges) == pytest.approx(100 * lo / wall)
    assert read("device.wait_hi_pct", edges) == pytest.approx(100 * hi / wall)
    assert 0 < read("device.wait_lo_pct", edges) \
        < read("device.wait_hi_pct", edges) < 100


def test_the_step_metrics_read_the_counts_and_uploads_the_edges_imply(edges):
    # decode turns: two, neither read a finished dispatch (one read nothing)
    assert read("step.decode_ready_readback_pct", edges) == 0.0
    assert read("step.decode_upload_ms", edges) == pytest.approx(2.0)
    # prefill turns: the one behind the decode found it done; the one that
    # only finishes and the serial one blocked
    assert read("step.prefill_ready_readback_pct", edges) \
        == pytest.approx(100 / 3)
    assert read("step.prefill_upload_ms", edges) == pytest.approx(2 / 3)


def test_a_wait_is_read_only_where_a_launch_starved(edges):
    before, _ = edges
    quiet = {k: v for k, v in before.items()}
    quiet["timeline.wall_seconds_total"] += 1.0
    quiet["timeline.launches_total"] += 40
    quiet["timeline.decode_steps_total"] += 40
    assert read("device.starved_launch_pct", (before, quiet)) == 0.0
    assert read("device.wait_lo_pct", (before, quiet)) == 0.0
    assert read("device.wait_hi_pct", (before, quiet)) == 0.0
    assert read("step.decode_ready_readback_pct", (before, quiet)) == 0.0
    # no prefill turn in the window: nothing to divide by, nothing reported
    assert read("step.prefill_upload_ms", (before, quiet)) is None


def test_a_program_without_the_counters_reads_nothing_and_does_not_raise(edges):
    new = ("launches", "device_wait", "ready_readbacks", "upload_seconds")
    parent = tuple({k: v for k, v in side.items()
                    if not any(n in k for n in new)} for side in edges)
    assert "timeline.wall_seconds_total" in parent[0]
    for name in SEVEN:
        assert read(name, parent) is None
        assert read(name, ({}, {})) is None
