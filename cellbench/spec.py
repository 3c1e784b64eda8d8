"""Where the benchmark's data lives and how a name becomes a file.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own under ``<root>/cellbench/``, found
by the name ``BENCHMARK.json`` gives it.  Code that a later PR may have to
add (a reader, a reference, a generator, an ops/bytes function) is a module
found the same way, by path, through the one lookup ``load_module`` — there
is no registry to edit.  ``root`` is an argument everywhere so that a test
can point the harness at a copy.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
PACKAGE = "cellbench"

# kinds of module a name can stand for; each is a directory under cellbench/
MODULE_KINDS = ("readers", "reference", "generators", "costs")


def data_dir(root: Path) -> Path:
    return Path(root) / PACKAGE


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path) -> dict:
    return read_json(Path(root) / "BENCHMARK.json")


def load_settings(root: Path) -> dict:
    return read_json(data_dir(root) / "settings.json")


def load_peaks(root: Path, device_kind: str) -> dict:
    """Published peaks of the device the run is on.  An unknown device is
    an error: a roofline share against a guessed peak is worse than none."""
    peaks = read_json(data_dir(root) / "peaks.json")
    if device_kind not in peaks:
        raise KeyError(
            f"device kind {device_kind!r} is not in cellbench/peaks.json "
            f"(known: {sorted(k for k in peaks if not k.startswith('_'))})")
    return peaks[device_kind]


def load_module(root: Path, kind: str, name: str):
    """``<root>/cellbench/<kind>/<name>.py`` as a module, loaded by path so
    that a data root outside the package can add one."""
    if kind not in MODULE_KINDS:
        raise ValueError(f"unknown module kind {kind!r}")
    path = data_dir(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module named {name!r}: {path}")
    key = f"_cellbench_{kind}_{name}_{abs(hash(str(path.resolve())))}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    name: str
    chips: int
    config: dict        # cellbench/configs/<config>.json
    traffic: dict       # cellbench/traffic/<traffic>.json, cell overrides applied
    params: dict        # cellbench/cells/<name>.json ({} when absent)


def load_cell(root: Path, workload: str) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(
            f"no workload {workload!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(Path(root) / cfg_entry["file"])
    traffic = read_json(data_dir(root) / "traffic" / f"{entry['traffic']}.json")
    cell_file = data_dir(root) / "cells" / f"{workload}.json"
    params = read_json(cell_file) if cell_file.is_file() else {}
    # what differs between two cells on one traffic file (the offered rate
    # below this configuration's knee) lives with the cell
    traffic = {**traffic, **params.get("traffic_overrides", {})}
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config,
        traffic=traffic, params=params)


def metrics_for(root: Path, workload: str, section: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    bench = load_benchmark(root)
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_layer_metric(root: Path, name: str) -> dict:
    return read_json(data_dir(root) / "layer_metrics" / f"{name}.json")
