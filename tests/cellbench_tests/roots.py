"""Build a data root for the tests: a copy of cellbench's data directories
with the tiny fixtures of ``data/`` laid over them and a BENCHMARK.json of
tiny cells.  No file of the real benchmark is changed; the harness is pointed
at the copy with ``--root``."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent.parent
DATA_DIRS = ("configs", "traffic", "layer_metrics", "cells", "readers",
             "reference", "generators", "costs")


def tiny_benchmark() -> dict:
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    return {
        **real,
        "configs": [
            {"name": "tiny-dense", "source": "test fixture",
             "file": "cellbench/configs/tiny-dense.json", "reduced": [],
             "why": "toy dense decoder"},
            {"name": "tiny-moe", "source": "test fixture",
             "file": "cellbench/configs/tiny-moe.json", "reduced": [],
             "why": "toy mixture of experts"},
        ],
        "workloads": [
            {"name": "tiny-dense.open", "config": "tiny-dense",
             "traffic": "tiny-open", "chips": 1, "why": "open loop, toy"},
            {"name": "tiny-moe.closed", "config": "tiny-moe",
             "traffic": "tiny-closed", "chips": 1, "why": "closed loop, toy"},
        ],
        # end_to_end and per_layer are the real benchmark's, as they stand:
        # a cell that only adds an entry reports what they list for every cell
    }


def build(root: Path) -> Path:
    dst = root / "cellbench"
    for d in DATA_DIRS:
        shutil.copytree(REPO / "cellbench" / d, dst / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "cellbench" / "peaks.json", dst / "peaks.json")
    shutil.copy(HERE / "data" / "settings.json", dst / "settings.json")
    for name in ("tiny-dense", "tiny-moe"):
        shutil.copy(HERE / "data" / f"{name}.json", dst / "configs")
    for name in ("tiny-open", "tiny-closed"):
        shutil.copy(HERE / "data" / f"{name}.json", dst / "traffic")
    (root / "BENCHMARK.json").write_text(json.dumps(tiny_benchmark(), indent=1))
    return root
