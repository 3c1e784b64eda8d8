"""The whole run, end to end, on the CPU at a tiny size: ``--rehearse`` under
``--trace 0`` and ``--trace 1`` gives the contract's line and the same
``correct``; and (section 7 of the issue) a configuration, a traffic mix, a
per-layer metric with its reader and a cell are added as new files alone."""

import json
import os
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, workload, trace, seconds=1.5, extra=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(roots.REPO)}
    p = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", workload,
         "--seed", str(2**31 + 11), "--seconds", str(seconds),
         "--trace", str(trace), "--root", str(root), *extra],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=300)
    return p, p.stdout.strip().splitlines()


# The runs are made in fixtures: a run is a whole process that starts jax,
# and with six workers busy it can pass the per-test time that tests/conftest.py
# allows a test's own body.

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return roots.build(tmp_path_factory.mktemp("root"))


@pytest.fixture(scope="module")
def refused(root):
    return run_cell(root, "tiny-dense.open", 0)


@pytest.fixture(scope="module")
def rehearsed(root):
    return {trace: run_cell(root, "tiny-dense.open", trace, extra=["--rehearse"])
            for trace in (0, 1)}


def test_without_a_tpu_the_run_refuses(refused):
    p, lines = refused
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in lines)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(root, rehearsed, trace):
    p, lines = rehearsed[trace]
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(lines[-1])
    assert CONTRACT_KEYS <= set(out) <= CONTRACT_KEYS | {"breakdown"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if trace:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert set(out["metrics"]) <= {m["name"] for m in bench["per_layer"]}
        assert "step.wall_ms" in out["metrics"] and "breakdown" in out
        # off the chip there are no peaks, so no share of a roofline
        assert not any(n.endswith("_roofline") for n in out["metrics"])
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in out["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_both_rehearsals_find_the_same_correct(rehearsed):
    a, b = (json.loads(lines[-1]) for _, lines in rehearsed.values())
    assert a["correct"] is b["correct"] is True


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """Section 7: copy the data, add one configuration, one traffic mix, one
    per-layer metric with a reader of its own, one cell — and run it."""
    root = roots.build(tmp_path_factory.mktemp("added"))
    before = {p: p.read_bytes() for p in (root / "cellbench").rglob("*")
              if p.is_file()}
    bench_before = json.loads((root / "BENCHMARK.json").read_text())
    cb = root / "cellbench"
    config = json.loads((cb / "configs" / "tiny-dense.json").read_text())
    config["served_name"] = "added"
    (cb / "configs" / "added.json").write_text(json.dumps(config))
    traffic = json.loads((cb / "traffic" / "tiny-open.json").read_text())
    traffic["rate_rps"] = 5.0
    (cb / "traffic" / "added-mix.json").write_text(json.dumps(traffic))
    (cb / "readers" / "added_reader.py").write_text(
        "def read(ctx, args):\n"
        "    return float(sum(r['n_tokens'] for r in ctx['records'])) * args['k']\n")
    (cb / "layer_metrics" / "added.tokens.json").write_text(json.dumps({
        "name": "added.tokens", "layer": "load generator", "unit": "tokens",
        "better": "higher", "source": "host_clock", "moves": "tok_s_chip",
        "reader": "added_reader", "args": {"k": 2.0}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "added", "source": "test",
                             "file": "cellbench/configs/added.json",
                             "reduced": [], "why": "added by a test"})
    bench["workloads"].append({"name": "added.cell", "config": "added",
                               "traffic": "added-mix", "chips": 1,
                               "why": "added by a test"})
    bench["per_layer"].append({
        "name": "added.tokens", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "tok_s_chip", "workloads": ["added.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run = run_cell(root, "added.cell", 1, seconds=1, extra=["--rehearse"])
    other = run_cell(root, "tiny-dense.open", 1, seconds=1, extra=["--rehearse"])
    return root, before, bench_before, run, other


def test_a_cell_is_added_with_new_files_only(added):
    root, before, bench_before, (p, lines), _ = added
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["metrics"]["added.tokens"]["value"] > 0
    assert all(path.read_bytes() == data for path, data in before.items())
    # the entries that were there are untouched, and still the new cell gets
    # every end-to-end metric and every per-layer metric of the real benchmark
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[section][:len(bench_before[section])] == bench_before[section]
    real = json.loads((roots.REPO / "BENCHMARK.json").read_text())
    assert bench_before["end_to_end"] == real["end_to_end"]
    assert bench_before["per_layer"] == real["per_layer"]
    assert ([m["name"] for m in spec.metrics_for(root, "added.cell", "end_to_end")]
            == [m["name"] for m in real["end_to_end"]])
    got = set(out["metrics"])
    assert {"step.wall_ms", "client.late_ms_p95", "device.idle_pct"} <= got
    assert got - {"added.tokens"} <= {m["name"] for m in real["per_layer"]}
    # a cell of the first benchmark does not get the newcomer's metric
    assert "added.tokens" not in {
        m["name"] for m in spec.metrics_for(root, "tiny-dense.open", "per_layer")}


def test_a_metric_that_lists_one_cell_is_on_that_cell_s_line_alone(added):
    """``added.tokens`` was appended after the last entry of ``per_layer``
    with ``"workloads": ["added.cell"]``: the traced line of a cell that was
    there before holds what it held and not the newcomer's metric."""
    root, _, bench_before, (_, mine), (p, lines) = added
    assert p.returncode == 0, p.stderr[-2000:]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert bench["per_layer"][-1]["name"] == "added.tokens"
    assert bench["per_layer"][-1]["workloads"] == ["added.cell"]
    other, own = json.loads(lines[-1])["metrics"], json.loads(mine[-1])["metrics"]
    assert "added.tokens" in own and "added.tokens" not in other
    assert set(other) <= {m["name"] for m in bench_before["per_layer"]}
    assert {"step.wall_ms", "sched.ahead_dispatch_pct"} <= set(other)
    # nor the metrics the real benchmark keeps for its four-chip cell
    assert not any(n.startswith("coll.") for n in set(own) | set(other))
