"""Metrics-plane static analysis (dtmet) tests: THE tenth tier-1 gate
(zero non-accepted findings over the extracted producer→renderer→
scraper census against the committed metrics manifest), the census/
registry/docs drift contract, the renamed-counter injection proof, and
each MT001–MT005 rule on bad/good fixtures under tests/lint_fixtures/.
"""

import argparse
import copy
import io
import json
import shutil
import time
from pathlib import Path

import pytest

from dynamo_tpu.analysis.metcheck import (
    DEFAULT_METRICS_MANIFEST_PATH,
    DOCS_BEGIN,
    DOCS_END,
    MET_RULES,
    census_snapshot,
    check_metric_facts,
    collect_metric_facts,
    render_docs_table,
    run_metrics,
)
from dynamo_tpu.analysis.tracecheck import Manifest, TraceFinding
from dynamo_tpu.obs.metric_names import SCHEMA, EngineMetric as EM

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "lint_fixtures"

# the fixtures' widget surface, for run_metrics tests where the real
# SCHEMA would drown everything in registry drift
_WIDGET_SCHEMA = {
    "dynamo_tpu_widget_dispatches_total": ("counter", ()),
    "dynamo_tpu_widget_orphaned": ("gauge", ()),
}


def _registry():
    return {name: (typ, list(labels))
            for name, (typ, labels) in SCHEMA.items()}


def _rules(findings):
    return {f.rule for f in findings}


def _fixture_findings(path):
    """Findings for one fixture file with MT005 self-suppressed via a
    census self-snapshot (fixtures test the site rules, not drift)."""
    facts, intrinsic = collect_metric_facts([path], root=FIXTURES)
    manifest = Manifest(entrypoints=census_snapshot(facts))
    return facts, check_metric_facts(facts, manifest, intrinsic)


# ------------------------------------------------------------- the gate ----


@pytest.fixture(scope="module")
def real():
    t0 = time.perf_counter()
    facts, intrinsic = collect_metric_facts()
    elapsed = time.perf_counter() - t0
    docs_text = (ROOT / "docs" / "observability.md").read_text()
    return facts, intrinsic, docs_text, elapsed


def _real_findings(real, manifest):
    facts, intrinsic, docs_text, _ = real
    return check_metric_facts(facts, manifest, intrinsic,
                              registry=_registry(), docs_text=docs_text)


def test_metrics_gate_zero_nonaccepted_findings(real):
    """THE tier-1 metrics-plane gate: every rendered metric, scrape
    site and engine-dict read is clean against the committed metrics
    manifest, the metric_names registry and the generated docs table.
    If this fails you either fix the drift (a renamed series, a stale
    scrape literal, dead telemetry — preferred) or, for a justified
    by-design deviation, re-snapshot with `dynamo-tpu lint --metrics
    --update-baseline` and justify the new accepted entry."""
    manifest = Manifest.load(DEFAULT_METRICS_MANIFEST_PATH)
    assert manifest.entrypoints, "metrics manifest missing or empty"
    fresh = manifest.filter(_real_findings(real, manifest))
    assert not fresh, (
        "non-accepted metrics-plane findings:\n  "
        + "\n  ".join(f.render() for f in fresh)
        + "\nFix the drift, or re-snapshot via `dynamo-tpu lint "
        "--metrics --update-baseline` and add a justification "
        "(docs/static_analysis.md#metrics-plane)."
    )


def test_metrics_gate_is_fast(real):
    """Acceptance bound from the issue: the tenth gate's fact
    collection stays well under 15s (it shares core.parse_module's
    cache with the other nine passes)."""
    *_, elapsed = real
    assert elapsed <= 15.0, f"metrics fact collection took {elapsed:.1f}s"


def test_manifest_accepted_entries_justified_and_live(real):
    """Every accepted entry carries a real justification and still
    matches a current finding — shared contract in
    tests/manifest_hygiene.py (metcheck keys entries on the metric
    name, carried in the entrypoint field)."""
    from manifest_hygiene import assert_manifest_hygiene

    manifest = Manifest.load(DEFAULT_METRICS_MANIFEST_PATH)
    assert_manifest_hygiene(
        manifest, _real_findings(real, manifest),
        entity_field="entrypoint")


def test_census_matches_registry_exactly(real):
    """The extracted census IS the registry: every SCHEMA name is
    rendered and every rendered name is declared.  (The gate enforces
    this via MT005 registry findings; this pins it directly so a
    future accepted entry can't quietly grandfather a gap.)"""
    facts, *_ = real
    assert set(facts["metrics"]) == set(SCHEMA)


def test_consumers_resolve_through_the_registry(real):
    """The typed scrape layer shows up as consumers by NAME (registry
    references resolve through the const table), and the bench summary
    keys it feeds all sit on rendered metrics."""
    facts, *_ = real
    sites = facts["consumers"].get(EM.PREFILL_DISPATCHES_TOTAL)
    assert sites and any("benchmarks/scrape.py" in s for s in sites), sites
    assert set(facts["consumers"]) <= set(facts["metrics"])
    engine = facts["engine"]
    assert engine["keys"], "EngineCore.metrics() keys not extracted"
    assert set(engine["consumers"]) <= set(engine["keys"])


def test_renamed_counter_is_caught_at_the_scrape_site(real):
    """THE scenario this plane exists for: rename a rendered counter
    (drop it from the census) and MT002 must fire naming the exact
    stale scrape site in benchmarks/scrape.py — the bench column would
    otherwise silently zero."""
    facts, *_ = real
    broken = copy.deepcopy(facts)
    del broken["metrics"][EM.PREFILL_DISPATCHES_TOTAL]
    findings = check_metric_facts(broken, Manifest(), [], drift=False)
    hits = [f for f in findings
            if f.rule == "MT002"
            and f.entrypoint == EM.PREFILL_DISPATCHES_TOTAL]
    assert hits, [f.render() for f in findings]
    assert any("benchmarks/scrape.py" in f.key for f in hits), (
        [f.key for f in hits])


# ------------------------------------------------------- rule fixtures ----


@pytest.mark.parametrize("rule", ["MT001", "MT002", "MT003", "MT004"])
def test_rule_fixtures(rule):
    n = int(rule[-3:])
    bad = FIXTURES / f"mt{n:03d}_bad.py"
    good = FIXTURES / f"mt{n:03d}_good.py"
    _, bad_findings = _fixture_findings(bad)
    _, good_findings = _fixture_findings(good)
    assert rule in _rules(bad_findings), (
        f"{bad.name} should trip {rule}, got "
        + str([f.render() for f in bad_findings]))
    assert rule not in _rules(good_findings), (
        f"{good.name} should be clean of {rule}, got "
        + str([f.render() for f in good_findings]))


def test_mt004_flags_all_three_misuses():
    """The bad fixture packs a non-_total counter, a millisecond
    histogram and a decremented counter — all three keys fire."""
    _, findings = _fixture_findings(FIXTURES / "mt004_bad.py")
    keys = {f.key for f in findings if f.rule == "MT004"}
    assert {"counter-name", "histogram-units", "decremented-counter"} <= keys


def test_mt005_census_drift_fixture_pair():
    """A manifest snapshotted from the base side flags exactly the
    four drifts on the drift side: added, removed, retyped, relabeled."""
    base_facts, base_intr = collect_metric_facts(
        [FIXTURES / "mt005_base.py"], root=FIXTURES)
    drift_facts, _ = collect_metric_facts(
        [FIXTURES / "mt005_drift.py"], root=FIXTURES)
    manifest = Manifest(entrypoints=census_snapshot(base_facts))
    assert not check_metric_facts(base_facts, manifest, base_intr)
    findings = check_metric_facts(drift_facts, manifest, [])
    assert [(f.entrypoint, f.rule, f.key) for f in findings] == [
        ("dynamo_tpu_widget_new_total", "MT005", "added"),
        ("dynamo_tpu_widget_old_total", "MT005", "removed"),
        ("dynamo_tpu_widget_ops_total", "MT005", "labels"),
        ("dynamo_tpu_widget_ops_total", "MT005", "type"),
    ]


def test_mt005_first_snapshot_is_free():
    """An empty manifest (no committed census yet) raises no drift."""
    facts, _ = collect_metric_facts(
        [FIXTURES / "mt005_base.py"], root=FIXTURES)
    assert "MT005" not in _rules(check_metric_facts(facts, Manifest(), []))


def test_mt005_registry_cross_check():
    """census vs obs/metric_names SCHEMA: missing, unrendered, retyped
    and relabeled declarations each get their own MT005 key."""
    facts, _ = collect_metric_facts(
        [FIXTURES / "mt005_base.py"], root=FIXTURES)
    manifest = Manifest(entrypoints=census_snapshot(facts))

    exact = {"dynamo_tpu_widget_ops_total": ("counter", ["phase"]),
             "dynamo_tpu_widget_old_total": ("counter", [])}
    assert not check_metric_facts(facts, manifest, [], registry=exact)

    drifted = {"dynamo_tpu_widget_ops_total": ("gauge", ["kind"]),
               "dynamo_tpu_widget_ghost_total": ("counter", [])}
    keys = {(f.entrypoint, f.key) for f in check_metric_facts(
        facts, manifest, [], registry=drifted) if f.rule == "MT005"}
    assert keys == {
        ("dynamo_tpu_widget_old_total", "registry-missing"),
        ("dynamo_tpu_widget_ghost_total", "registry-unrendered"),
        ("dynamo_tpu_widget_ops_total", "registry-type"),
        ("dynamo_tpu_widget_ops_total", "registry-labels"),
    }


def test_mt005_docs_table_cross_check():
    """docs/observability.md: absent markers and a stale generated
    table are both census drift; the regenerated table is clean."""
    facts, _ = collect_metric_facts(
        [FIXTURES / "mt005_base.py"], root=FIXTURES)
    manifest = Manifest(entrypoints=census_snapshot(facts))

    def docs_keys(text):
        return {f.key for f in check_metric_facts(
            facts, manifest, [], docs_text=text) if f.rule == "MT005"}

    good = f"prose\n{DOCS_BEGIN}\n{render_docs_table(facts['metrics'])}{DOCS_END}\n"
    assert docs_keys(good) == set()
    assert docs_keys("prose with no markers") == {"docs-markers"}
    stale = f"{DOCS_BEGIN}\n| metric | type | labels |\n{DOCS_END}"
    assert docs_keys(stale) == {"docs-table"}


def test_mt005_docs_counts_listing_cross_check(real):
    """The listing of what an engine counts is generated from the
    registry's table: a stale one between its markers is census drift,
    the committed one is clean."""
    from dynamo_tpu.analysis.metcheck import COUNTS_BEGIN, COUNTS_END
    from dynamo_tpu.obs.metric_names import ENGINE_COUNTS

    facts, _, docs_text, _ = real
    listing = facts["engine"]["listing"]
    assert all(f"`{e.name}`" in listing for e in ENGINE_COUNTS if e.name)
    head, rest = docs_text.split(COUNTS_BEGIN, 1)
    stale = head + COUNTS_BEGIN + "\n| gone |\n" + COUNTS_END \
        + rest.split(COUNTS_END, 1)[1]

    def docs_keys(text):
        return {f.key for f in check_metric_facts(
            facts, Manifest(entrypoints=census_snapshot(facts)), [],
            docs_text=text) if f.rule == "MT005"}

    assert docs_keys(docs_text) == set()
    assert docs_keys(stale) == {"docs-counts"}


def test_rule_table_complete():
    assert sorted(MET_RULES) == [f"MT00{i}" for i in range(1, 6)]


# --------------------------------------------------- update + CLI contract ----


def _args(**kw):
    base = dict(paths=None, fmt="text", select=None, baseline=None,
                no_baseline=False, update_baseline=False, root=None,
                project=False, trace=False, wire=False, perf=False,
                shard=False, proto=False, load=False, kern=False,
                metrics=True, manifest=None, changed=False)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.fixture()
def widget_root(tmp_path, monkeypatch):
    """A scan root holding only the MT001 fixture pair's bad side
    (under dynamo_tpu/ — run_metrics scans the package dirs, and
    producer scope excludes tests/benchmarks), with SCHEMA pinned to
    the widget surface so run_metrics sees no registry noise from the
    real 66-metric registry."""
    (tmp_path / "dynamo_tpu").mkdir()
    shutil.copy(FIXTURES / "mt001_bad.py",
                tmp_path / "dynamo_tpu" / "mt001_bad.py")
    # only the rendered name: the bad side never renders orphaned, and a
    # registry-unrendered MT005 would (correctly) keep the root red
    monkeypatch.setattr(
        "dynamo_tpu.obs.metric_names.SCHEMA",
        {"dynamo_tpu_widget_dispatches_total": ("counter", ())})
    return tmp_path


def test_update_roundtrip_carries_justifications(widget_root):
    """finding -> exit 1 -> --update accepts it (TODO) -> justify ->
    second --update carries the justification by key -> gate green."""
    mpath = widget_root / "manifest.json"
    args = lambda **kw: _args(root=str(widget_root),
                              manifest=str(mpath), **kw)
    assert run_metrics(args(), out=io.StringIO()) == 1       # MT001

    assert run_metrics(args(update_baseline=True),
                       out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert "dynamo_tpu_widget_dispatches_total" in doc["entrypoints"]
    assert [e["justification"] for e in doc["accepted"]] == [
        "TODO: justify"]
    assert [e["rule"] for e in doc["accepted"]] == ["MT001"]

    doc["accepted"][0]["justification"] = "kept: debug-only family"
    mpath.write_text(json.dumps(doc))
    assert run_metrics(args(), out=io.StringIO()) == 0  # accepted

    assert run_metrics(args(update_baseline=True),
                       out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert [e["justification"] for e in doc["accepted"]] == [
        "kept: debug-only family"]


def test_json_output_stable_sorted(widget_root):
    outs = []
    for _ in range(2):
        out = io.StringIO()
        run_metrics(_args(root=str(widget_root), fmt="json",
                          manifest=str(widget_root / "m.json")), out=out)
        outs.append(out.getvalue())
    assert outs[0] == outs[1]
    doc = json.loads(outs[0])
    assert {"findings", "accepted", "total", "metrics"} <= set(doc)
    assert doc["findings"] == sorted(
        doc["findings"],
        key=lambda f: (f["entrypoint"], f["rule"], f["key"]))


def test_cli_routes_metrics_flag(tmp_path, monkeypatch):
    """`dynamo-tpu lint --metrics` reaches run_metrics (not the file
    pass), and a clean widget surface exits 0."""
    from dynamo_tpu.analysis.cli import run_lint

    (tmp_path / "dynamo_tpu").mkdir()
    shutil.copy(FIXTURES / "mt001_good.py",
                tmp_path / "dynamo_tpu" / "mt001_good.py")
    monkeypatch.setattr("dynamo_tpu.obs.metric_names.SCHEMA",
                        dict(_WIDGET_SCHEMA))
    out = io.StringIO()
    rc = run_lint(_args(root=str(tmp_path),
                        manifest=str(tmp_path / "m.json")), out=out)
    assert rc == 0
    assert "metrics finding" in out.getvalue()


def test_changed_skip_when_plane_untouched(widget_root, monkeypatch):
    """`lint --changed`: the metrics pass skips when no metrics-plane
    input changed (and the skip is explicit in the output)."""
    import dynamo_tpu.analysis.metcheck as mc

    monkeypatch.setattr(mc, "_metrics_affected", lambda root: False)
    out = io.StringIO()
    rc = run_metrics(_args(root=str(widget_root), changed=True,
                           manifest=str(widget_root / "m.json")), out=out)
    assert rc == 0
    assert "unaffected" in out.getvalue()


def test_manifest_filter_is_a_multiset():
    f = TraceFinding("dynamo_tpu_widget_ops_total", "MT001", "k", "d")
    m = Manifest(accepted=[{"entrypoint": "dynamo_tpu_widget_ops_total",
                            "rule": "MT001", "key": "k"}])
    assert m.filter([f]) == []
    assert m.filter([f, f]) == [f]  # budget of one covers one
