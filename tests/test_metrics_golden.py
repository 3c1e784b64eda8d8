"""Golden /metrics render regression tests: committed byte-level
exposition recordings (tests/metrics_golden/, regenerate with
`python tests/metrics_golden/generate.py`) re-rendered by CURRENT code
from the same deterministic seeding, and re-scraped through the typed
helpers in benchmarks/scrape.py.

These are the render-side safety net the metrics manifest's MT005
census points at: a byte diff here means the exposition format changed
— every banked bench column and dashboard speaks the committed bytes,
so either restore the format or consciously regenerate (and let the
dtmet census snapshot the rename/retype).
"""

import importlib.util
import re
from pathlib import Path

import pytest

from dynamo_tpu.obs.metric_names import (
    EngineMetric as EM,
    KvTransferMetric as KM,
    SCHEMA,
)

GOLDEN = Path(__file__).parent / "metrics_golden"
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


@pytest.fixture(scope="module")
def gen():
    """The fixture generator module, loaded from its committed path —
    the test re-runs the exact seeding generate.py committed."""
    spec = importlib.util.spec_from_file_location(
        "metrics_golden_generate", GOLDEN / "generate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    yield mod
    mod.reset_producers()


def _sample_names(text: str) -> set[str]:
    names = set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"([A-Za-z_][A-Za-z0-9_]*)", line)
        assert m, f"unparseable exposition line: {line!r}"
        n = m.group(1)
        for suf in _HIST_SUFFIXES:
            if n.endswith(suf) and n[:-len(suf)] in SCHEMA:
                n = n[:-len(suf)]
        names.add(n)
    return names


# ------------------------------------------------------- byte equality ----


def test_http_render_matches_golden(gen):
    """Same seeding, current code, byte-identical exposition."""
    committed = (GOLDEN / "render_http.txt").read_text()
    assert gen.render_http() == committed


def test_components_render_matches_golden(gen):
    committed = (GOLDEN / "render_components.txt").read_text()
    assert gen.render_components() == committed


def test_golden_covers_the_whole_registry():
    """The two renders together expose EVERY registry name — a SCHEMA
    entry missing here is either unrendered (MT005 registry-unrendered)
    or the seeding stopped exercising its family."""
    names = _sample_names((GOLDEN / "render_http.txt").read_text())
    names |= _sample_names((GOLDEN / "render_components.txt").read_text())
    assert names == set(SCHEMA), (
        sorted(names - set(SCHEMA)), sorted(set(SCHEMA) - names))


# ------------------------------------------------- scrape round-trips ----


def test_prefill_dispatch_stats_round_trip():
    """Every summary key the bench banks, re-derived from the committed
    bytes, with hand-checked values from the fixed seeding."""
    from benchmarks.scrape import prefill_dispatch_stats_from_text

    stats = prefill_dispatch_stats_from_text(
        (GOLDEN / "render_http.txt").read_text())
    assert stats == {
        "prefill_dispatches": 2,
        "prefill_tokens_per_dispatch": 80.0,
        "prefill_batch_occupancy": 3.0,
        "prefill_budget_utilization": 0.625,
        "unified_dispatches": 1,
        "unified_decode_rows_per_dispatch": 6.0,
        "unified_prefill_tokens_per_dispatch": 90.0,
        "unified_budget_utilization": 0.75,
        "persist_hits": 2,
        "persist_hit_rate": 0.6667,
        "persist_restored_tokens": 32,
        "persist_spill_bytes": 4096,
        "persist_resident_bytes": 8192,
        "host_gap_ms_per_turn": 2.5,
        "transfer_mbps_dcn": 240.0,
        "kv_stream_sessions": 1,
        "kv_stream_layers_sent": 2,
        "kv_stream_bytes": 4096,
        "kv_stream_fallbacks": 0,
        "kv_stream_overlap_ratio": 0.5,
    }


def test_perf_model_stats_round_trip():
    from benchmarks.scrape import perf_model_stats_from_text

    rows = perf_model_stats_from_text(
        (GOLDEN / "render_http.txt").read_text())
    # measured = dispatch -> readback returned (10 + 1 ms), per kind
    assert rows == {
        "step": {
            "predicted_dispatch_ms": 1.25,
            "measured_dispatch_ms": 11.0,
            "dispatches_total": 1.0,
            "model_error_ratio": 0.1136,
        },
        "decode_multi": {
            "measured_dispatch_ms": 11.0,
            "dispatches_total": 1.0,
        },
    }


def test_snapshot_parses_labeled_series():
    from benchmarks.scrape import MetricsSnapshot

    snap = MetricsSnapshot.parse((GOLDEN / "render_http.txt").read_text())
    assert snap.value(KM.MBPS, labels={"path": "dcn"}) == 240.0
    assert snap.value(KM.MBPS, labels={"path": "ici"}) == 1000.0
    assert snap.value(EM.STEP_PHASE_SECONDS_TOTAL,
                      labels={"phase": "dispatch"}) == 0.02
    assert len(snap.series(KM.CALLS_TOTAL)) == 2


# --------------------------------------------- unknown-metric tolerance ----


def test_snapshot_tolerates_surface_drift():
    """The scrape layer NEVER raises on drift: unknown names, malformed
    lines and non-numeric samples are skipped (drift fails in
    `lint --metrics`, not mid-benchmark) and absent lookups return the
    caller's default."""
    from benchmarks.scrape import MetricsSnapshot

    text = (GOLDEN / "render_http.txt").read_text() + (
        "dynamo_tpu_widget_bogus_total 3\n"      # not in the registry
        "garbage{unterminated 1\n"               # malformed
        f"{EM.STEPS_TOTAL} not-a-number\n"       # unparseable value
        "# EOF\n")
    snap = MetricsSnapshot.parse(text)
    assert "dynamo_tpu_widget_bogus_total" not in snap.names()
    assert snap.value("dynamo_tpu_widget_bogus_total", default=-1) == -1
    assert snap.value(EM.STEPS_TOTAL) == 2.0  # the real sample survives
    folded = set()
    for n in snap.names():
        for suf in _HIST_SUFFIXES:
            if n.endswith(suf) and n[:-len(suf)] in SCHEMA:
                n = n[:-len(suf)]
        folded.add(n)
    assert folded <= set(SCHEMA)


def test_scrape_helpers_return_none_off_surface():
    """A non-dynamo endpoint (or a pre-warm scrape) yields None, not a
    KeyError — serve_bench probes /metrics before the engine has
    dispatched anything."""
    from benchmarks.scrape import (perf_model_stats_from_text,
                                   prefill_dispatch_stats_from_text)

    assert prefill_dispatch_stats_from_text("") is None
    assert perf_model_stats_from_text("# TYPE foo counter\nfoo 1\n") is None
