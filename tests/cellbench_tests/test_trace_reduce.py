"""The reduction from trace rows to numbers, on rows made by hand and on a
small recorded trace (``data/recorded_trace.json``: the device operations and
the engine thread's events of a few steps, as the chip's profiler wrote them)."""

import json

import pytest

from cellbench import trace_reduce as tr
from roots import HERE

MS = 1_000_000      # ns


def test_union_and_self_times():
    assert tr.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    rows = [["while", 0, 10 * MS], ["fusion.1", 1 * MS, 3 * MS],
            ["attn_kernel", 5 * MS, 4 * MS], ["fusion.1", 20 * MS, 2 * MS]]
    t = tr.self_times(rows)
    assert t["while"] == pytest.approx(0.003)       # 10 - 3 - 4
    assert t["fusion.1"] == pytest.approx(0.005)
    assert t["attn_kernel"] == pytest.approx(0.004)
    assert tr.matching(t, "kernel$") == pytest.approx(0.004)


def test_idle_share_and_gap_attribution():
    dev = [["step", 0, 10 * MS], ["step", 14 * MS, 6 * MS], ["step", 30 * MS, 10 * MS]]
    host = [["engine.step", 9 * MS, 8 * MS], ["build", 10 * MS, 3 * MS],
            ["engine.step", 19 * MS, 13 * MS], ["post", 20 * MS, 4 * MS]]
    out = tr.reduce({"devices": {"d0": dev, "d1": dev}, "host": host}, gap_depth=1)
    assert out["window_s"] == pytest.approx(0.040)
    assert out["busy_s"] == pytest.approx(0.026)
    assert out["idle_pct"] == pytest.approx(35.0)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["build"] == pytest.approx(0.003)            # 10..13 of gap 10..14
    assert gaps["post"] == pytest.approx(0.004)             # 20..24 of gap 20..30
    assert gaps["engine.step"] == pytest.approx(0.001 + 0.006)
    assert sum(gaps.values()) == pytest.approx(0.014)
    assert dict(out["breakdown"]["device_ops"])["step"] == pytest.approx(0.026)
    # at depth 0 only the outermost events name the gaps
    flat = tr.reduce({"devices": {"d0": dev}, "host": host}, gap_depth=0)
    assert set(dict(flat["breakdown"]["idle_gaps"])) == {"engine.step"}


def test_between_steps_and_no_devices():
    dev = [["a", 0, MS], ["a", 5 * MS, MS]]
    out = tr.reduce({"devices": {"d": dev}, "host": []})
    assert dict(out["breakdown"]["idle_gaps"]) == {
        "(between engine steps)": pytest.approx(0.004)}
    assert tr.reduce({"devices": {}, "host": []}) == {}


def test_recorded_trace():
    rec = json.loads((HERE / "data" / "recorded_trace.json").read_text())
    out = tr.reduce(rec["rows"], gap_depth=rec["gap_depth"])
    assert out["idle_pct"] == pytest.approx(rec["expect"]["idle_pct"], abs=1e-6)
    assert out["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    ops = dict(out["breakdown"]["device_ops"])
    for name, sec in rec["expect"]["device_ops"].items():
        assert ops[name] == pytest.approx(sec)
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == rec["expect"]["top_gap"]
    total_gap = out["window_s"] - out["busy_s"]
    assert sum(gaps.values()) <= total_gap * (1 + 1e-9)
