"""BENCHMARK.json keeps to its contract, and every name in it finds its file."""

import json
import re

import pytest

from cellbench import spec
from roots import REPO

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "cellbench.run"]
    assert BENCH["paths"] == ["cellbench", "tests/cellbench_tests"]
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert "setup_s" in E2E
    for m in metrics:   # a share of a roofline is named as the contract names it
        assert ("roofline" in m["name"]) == m["name"].endswith("_roofline"), m
        assert not m["name"].endswith("_roofline") or m["unit"] == "%"


def test_a_new_cell_needs_no_edit_to_an_entry_that_is_there():
    """Every end-to-end metric is reported by every cell, and so is a
    per-layer metric without a ``workloads`` list: a cell added later as one
    more ``workloads`` entry gets all of them.  A per-layer metric of what
    only some configurations have (collectives across chips, a kernel of one
    kind of layer) lists its cells: a list is not empty, names accepted
    cells once each, and every one of them reports the metric it moves."""
    for m in BENCH["end_to_end"]:
        assert "workloads" not in m, m["name"]
    for m in BENCH["per_layer"]:
        if "workloads" not in m:
            continue
        listed = m["workloads"]
        assert isinstance(listed, list) and listed, m["name"]
        assert len(set(listed)) == len(listed) and set(listed) <= set(CELLS), m
        assert set(listed) <= set(cells_of(E2E[m["moves"]])), m["name"]


def test_configs_and_workloads():
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    assert len(cfgs) == len(BENCH["configs"]) <= 24
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("cellbench/")
        data = spec.read_json(REPO / c["file"])
        assert data["reduced"] == c["reduced"] and data["source"] == c["source"]
        assert all(NAME.match(k) and k in data for k in c["reduced"])
        for k in c["reduced"]:      # a width is never cut
            assert not re.search(r"(hidden_size|intermediate|_dim$|_rank$|"
                                 r"head_dim|experts_per_tok)", k), k
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs)) and len(set(CELLS)) == len(CELLS) <= 24
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert {w["config"] for w in BENCH["workloads"]} == set(cfgs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_and_reports_enough(cell):
    c = spec.load_cell(REPO, cell)
    assert c.config["chips"] == c.chips
    spec.load_module(REPO, "generators", c.traffic["generator"])
    spec.load_module(REPO, "reference", c.config["reference"])
    e2e = [m["name"] for m in spec.metrics_for(REPO, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.metrics_for(REPO, cell, "per_layer")
    if c.traffic["loop"] == "open":
        assert c.params["traffic_overrides"]["rate_rps"] > 0


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_reader_and_moves(metric):
    desc = spec.load_layer_metric(REPO, metric["name"])
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert desc[key] == metric[key], key
    assert "workloads" not in desc      # which cells: BENCHMARK.json alone says
    assert hasattr(spec.load_module(REPO, "readers", desc["reader"]), "read")
    moved = E2E[metric["moves"]]
    # the metric it should move is reported in every cell that reports it
    assert set(cells_of(metric)) <= set(cells_of(moved))
    assert set(cells_of(metric)) <= set(CELLS)


def test_layer_names_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)
    perf = (REPO / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
