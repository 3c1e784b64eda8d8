"""Protocol-plane (dtproto) tests: THE seventh tier-1 gate (zero
non-accepted findings from the pinned-seed deterministic exploration
against the committed proto manifest), the determinism contract (same
seed → byte-identical schedule traces), the crash-point matrix over the
coordinator WAL, the replay-token roundtrip, the bug-catching proof
(an intentionally reordered WAL truncate is found and reproduces from
its token), and the golden schedule fixtures under
tests/lint_fixtures/proto/.
"""

import argparse
import io
import json
import sys
import time
from pathlib import Path

import pytest

from dynamo_tpu.analysis.protocheck import (
    DEFAULT_PROTO_MANIFEST_PATH,
    PROTO_RULES,
    SCENARIOS,
    ProtoFinding,
    ProtoManifest,
    ScenarioReport,
    affected_scenarios,
    check_proto,
    decode_token,
    encode_token,
    explore_scenario,
    facts_from,
    first_violation,
    replay_token,
    run_one,
    run_proto,
)

ROOT = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).parent / "lint_fixtures" / "proto"


# ------------------------------------------------------------- the gate ----


@pytest.fixture(scope="module")
def reports():
    """The pinned-seed exploration of every scenario — the same sweep
    ``dynamo-tpu lint --proto`` runs at budget 1."""
    t0 = time.perf_counter()
    reps = [explore_scenario(sc) for sc in SCENARIOS.values()]
    return reps, time.perf_counter() - t0


def test_proto_gate_zero_nonaccepted_findings(reports):
    """THE tier-1 protocol-plane gate: every explored schedule and
    crash point of the real coordinator/queue/drain/persist protocols
    is clean against the committed proto manifest.  If this fails,
    either fix the protocol bug the replay token in the finding
    reproduces (preferred), or — for an accepted behavior change —
    re-snapshot with `dynamo-tpu lint --proto --update-baseline` and
    justify the new accepted entry."""
    reps, _ = reports
    manifest = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    assert manifest.scenarios, "proto manifest missing or empty"
    findings = check_proto(reps, manifest)
    fresh = manifest.filter(findings)
    assert not fresh, (
        "non-accepted protocol-plane findings:\n  "
        + "\n  ".join(f.render() for f in fresh)
        + "\nEach PR001/PR003 finding embeds a replay token — feed it "
        "to dynamo_tpu.analysis.protocheck.replay_token() to reproduce "
        "the exact interleaving.  For accepted drift, re-snapshot via "
        "`dynamo-tpu lint --proto --update-baseline` and justify "
        "(docs/static_analysis.md#protocol-plane)."
    )


def test_proto_gate_is_fast(reports):
    """Acceptance bound: the pinned budget-1 sweep (every scenario,
    every seed, the full crash matrix) stays inside the tier-1 wall:
    virtual time makes ~100 protocol executions cost seconds."""
    _, elapsed = reports
    assert elapsed <= 60.0, f"proto exploration took {elapsed:.1f}s"


def test_manifest_accepted_entries_justified_and_live(reports):
    """Every accepted entry carries a real justification and still
    matches a current finding (no stale grandfathering) — shared
    contract in tests/manifest_hygiene.py (proto keys entries on the
    scenario name)."""
    from manifest_hygiene import assert_manifest_hygiene

    reps, _ = reports
    manifest = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    assert_manifest_hygiene(
        manifest, check_proto(reps, manifest), entity_field="scenario")


def test_exploration_is_deterministic(reports):
    """PR002's own premise, asserted directly: re-running the base seed
    of every scenario produced a byte-identical schedule trace."""
    reps, _ = reports
    assert all(rep.deterministic for rep in reps)


def test_wal_crash_matrix_covered_and_clean(reports):
    """The coord.wal sweep actually exercised the crash surface: kills
    at WAL appends (all three disk modes), fsyncs, every compaction
    boundary and frame sends — and the real recovery held every
    durability invariant."""
    reps, _ = reports
    rep = next(r for r in reps if r.scenario == "coord.wal")
    assert first_violation(rep) is None
    crashed = {r.crash.label for r in rep.results if r.crash is not None}
    for label in ("wal.append.kv", "wal.append.qpush", "wal.fsync.qpush",
                  "wal.compact.write", "wal.compact.rename",
                  "wal.compact.done", "frame.send.reply"):
        assert label in crashed, f"no crash injected at {label}"
    modes = {r.crash.mode for r in rep.results if r.crash is not None}
    assert modes == {"proc", "power", "torn"}


# ------------------------------------------------------- determinism -------


def test_same_seed_byte_identical_traces():
    """Two fresh runs with the same seed produce byte-identical
    schedule traces and choice lists."""
    sc = SCENARIOS["coord.queue"]
    a = run_one(sc, 7)
    b = run_one(sc, 7)
    assert json.dumps(a.trace) == json.dumps(b.trace)
    assert a.choices == b.choices
    assert a.token == b.token


def test_different_seeds_explore_different_schedules():
    """The seed actually steers the scheduler — otherwise the sweep is
    one run in a trench coat."""
    sc = SCENARIOS["tcp.drain"]
    traces = {json.dumps(run_one(sc, s).trace) for s in range(4)}
    assert len(traces) > 1


# ------------------------------------------------------ replay tokens ------


def test_replay_token_roundtrip():
    payload = {"scenario": "coord.wal", "seed": 3, "bug": "x",
               "crash": {"kind": "crash", "label": "wal.append.kv",
                         "occurrence": 1, "mode": "torn", "conn": 0,
                         "after_frames": 0, "direction": "s2c"},
               "choices": [0, 2, 1, 5]}
    token = encode_token(payload)
    assert token.startswith("dtp1.")
    assert "=" not in token
    assert decode_token(token) == payload
    with pytest.raises(ValueError):
        decode_token("nope." + token)


def test_replay_reproduces_clean_run():
    sc = SCENARIOS["coord.reconnect"]
    orig = run_one(sc, 1)
    again = replay_token(orig.token)
    assert again.trace == orig.trace
    assert again.violations == orig.violations


# ------------------------------------------------- the bug-catch proof -----


def test_reordered_wal_truncate_is_caught_and_replays():
    """The checker finds an intentionally reintroduced WAL-compaction
    bug (truncate-in-place before rewrite) via its crash matrix, and
    the finding's replay token reproduces the violation exactly."""
    rep = explore_scenario(SCENARIOS["coord.wal"], bug="reorder-truncate")
    bad = first_violation(rep)
    assert bad is not None, "reordered WAL truncate went undetected"
    assert any(v in ("kv_acked_durable", "queue_acked_durable",
                     "blob_acked_durable", "wal_version_head")
               for v, _ in bad.violations)
    assert bad.crash is not None
    again = replay_token(bad.token)
    assert again.violations == bad.violations
    assert again.trace == bad.trace


def test_racy_drain_is_caught_by_schedule_exploration():
    """A wait_idle that trusts the idle event's wake without re-reading
    the live count survives straight-line tests; the seeded schedule
    sweep finds the interleaving that breaks it."""
    rep = explore_scenario(SCENARIOS["tcp.drain"], bug="racy-drain")
    bad = first_violation(rep)
    assert bad is not None, "racy drain went undetected"
    assert any(v == "drain_zero_inflight" for v, _ in bad.violations)


def test_stranded_pull_is_caught_by_sever_matrix():
    """The pre-fix QUEUE_PULL (register into _pending_acks without
    checking the puller's conn is alive) loses a message when the
    consumer is severed mid-long-poll — the exact bug the plane found
    in the real dispatcher."""
    rep = explore_scenario(SCENARIOS["coord.queue"], bug="stranded-pull")
    bad = first_violation(rep)
    assert bad is not None, "stranded queue-pull went undetected"
    assert any(v == "queue_no_lost" for v, _ in bad.violations)


def test_stale_generation_shard_is_caught_and_replays():
    """A shard replica that echoes the *request's* generation instead of
    its own forges currency: after a handoff its pre-rebind holder data
    passes the gather fence and inflates the merged overlap scores.  The
    seeded schedule sweep catches the overcount and the finding's replay
    token reproduces it exactly."""
    rep = explore_scenario(SCENARIOS["router.shard"],
                           bug="stale-generation")
    bad = first_violation(rep)
    assert bad is not None, "stale-generation shard went undetected"
    assert any(v == "shard_no_stale_overcount" for v, _ in bad.violations)
    again = replay_token(bad.token)
    assert again.violations == bad.violations
    assert again.trace == bad.trace


# -------------------------------------------------- golden fixtures --------


def _load_fixtures():
    return sorted(FIXTURES.glob("*.json"))


def test_fixture_inventory():
    """One passing + one violating golden schedule per scenario."""
    names = {p.name for p in _load_fixtures()}
    for scen in SCENARIOS:
        stem = scen.replace(".", "_")
        assert f"{stem}_pass.json" in names
        assert f"{stem}_violate.json" in names


@pytest.mark.parametrize("path", _load_fixtures(),
                         ids=lambda p: p.stem)
def test_golden_fixture_replays(path):
    """Each committed replay token still reproduces its recorded
    outcome and violation set against today's protocol code.  A token
    indexes DetLoop's ready list, whose content is asyncio's: it holds
    for the minor version of Python it was recorded under
    (tests/lint_fixtures/proto/record.py records them anew)."""
    doc = json.loads(path.read_text())
    r = replay_token(doc["token"])
    assert r.outcome == doc["expect"]["outcome"], (
        f"{doc['name']}: {r.error} (recorded under Python "
        f"{doc['recorded_with']}, run under {sys.version.split()[0]})")
    assert sorted({v for v, _ in r.violations}) == \
        doc["expect"]["violations"], doc["name"]


# ---------------------------------------------------- rules & manifest -----


def test_rule_registry_documented():
    assert set(PROTO_RULES) == {"PR001", "PR002", "PR003", "PR004",
                                "PR005"}
    for code, text in PROTO_RULES.items():
        assert text, code


def test_nondeterminism_raises_pr002():
    rep = ScenarioReport("coord.wal", [run_one(SCENARIOS["coord.wal"], 0)],
                         deterministic=False)
    findings = check_proto([rep], ProtoManifest(), drift=False)
    assert ("coord.wal", "PR002", "determinism") in {
        f.accept_key for f in findings}


def test_state_machine_drift_raises_pr004(reports):
    """Removing a committed transition (or observing a new one) against
    the manifest surfaces as PR004 with the channel+edge key."""
    reps, _ = reports
    manifest = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    doctored = ProtoManifest(
        json.loads(json.dumps(manifest.scenarios)), [], {})
    chans = doctored.scenarios["coord.wal"]["channels"]
    ch = next(iter(chans))
    removed = chans[ch]["edges"].pop()
    chans[ch]["edges"].append("ghost>edge")
    findings = check_proto(reps, doctored)
    keys = {f.key for f in findings if f.rule == "PR004"
            and f.scenario == "coord.wal"}
    assert f"{ch}+{removed}" in keys
    assert f"{ch}-ghost>edge" in keys


def test_crash_census_drift_raises_pr005(reports):
    reps, _ = reports
    manifest = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    doctored = ProtoManifest(
        json.loads(json.dumps(manifest.scenarios)), [], {})
    doctored.scenarios["coord.wal"]["crash_points"]["wal.append.ghost"] = 1
    findings = check_proto(reps, doctored)
    assert ("coord.wal", "PR005", "-wal.append.ghost") in {
        f.accept_key for f in findings}


def test_accepted_entry_budget_is_a_multiset():
    m = ProtoManifest(accepted=[
        {"scenario": "s", "rule": "PR001", "key": "inv",
         "justification": "known"},
    ])
    f1 = ProtoFinding("s", "PR001", "inv", "a")
    f2 = ProtoFinding("s", "PR001", "inv", "b")
    fresh = m.filter([f1, f2])
    assert len(fresh) == 1   # one accepted entry absorbs exactly one


def test_update_baseline_carries_justifications(tmp_path):
    prev = ProtoManifest(accepted=[
        {"scenario": "s", "rule": "PR001", "key": "inv",
         "detail": "old", "justification": "because physics"},
    ])
    nxt = ProtoManifest.from_facts(
        {"s": {}}, [ProtoFinding("s", "PR001", "inv", "new")], prev)
    assert nxt.accepted[0]["justification"] == "because physics"
    nxt2 = ProtoManifest.from_facts(
        {"s": {}}, [ProtoFinding("s", "PR001", "other", "x")], prev)
    assert nxt2.accepted[0]["justification"] == "TODO: justify"
    path = tmp_path / "m.json"
    nxt.save(path)
    assert ProtoManifest.load(path).accepted == nxt.accepted


def test_manifest_json_is_stable(tmp_path):
    m = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    path = tmp_path / "again.json"
    m.save(path)
    assert json.loads(path.read_text())["scenarios"] == m.scenarios


# -------------------------------------------------------- CLI surface ------


def _args(**kw):
    base = dict(proto=True, changed=False, manifest=None, fmt="text",
                update_baseline=False, root=str(ROOT))
    base.update(kw)
    return argparse.Namespace(**base)


def test_run_proto_exit_codes(tmp_path):
    """Clean committed manifest → 0; a doctored manifest (ghost crash
    point) → 1 with the PR005 finding rendered."""
    out = io.StringIO()
    assert run_proto(_args(), out) == 0
    assert "0 protocol findings" in out.getvalue()

    doctored = ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH)
    doctored.scenarios["coord.wal"]["crash_points"]["wal.append.ghost"] = 1
    mpath = tmp_path / "doctored.json"
    doctored.save(mpath)
    out = io.StringIO()
    assert run_proto(_args(manifest=str(mpath)), out) == 1
    assert "PR005" in out.getvalue()


def test_run_proto_json_output():
    out = io.StringIO()
    assert run_proto(_args(fmt="json"), out) == 0
    doc = json.loads(out.getvalue())
    assert doc["findings"] == []
    assert sorted(doc["scenarios"]) == sorted(SCENARIOS)
    assert doc["runs"] > 50


def test_changed_maps_dirty_files_to_scenarios(monkeypatch):
    """`lint --proto --changed` maps dirty protocol files to the
    scenarios that execute them."""
    from dynamo_tpu.analysis import cli as cli_mod

    monkeypatch.setattr(
        cli_mod, "_git_changed_paths",
        lambda root: [ROOT / "dynamo_tpu" / "llm" / "kv" / "persist.py"])
    assert affected_scenarios(ROOT) == ["kv.persist"]

    monkeypatch.setattr(
        cli_mod, "_git_changed_paths",
        lambda root: [ROOT / "dynamo_tpu" / "runtime" / "transports"
                      / "tcp.py"])
    assert affected_scenarios(ROOT) == ["tcp.drain"]

    monkeypatch.setattr(
        cli_mod, "_git_changed_paths",
        lambda root: [ROOT / "dynamo_tpu" / "analysis" / "detloop.py"])
    assert affected_scenarios(ROOT) == list(SCENARIOS)


def test_update_baseline_refuses_partial_runs(monkeypatch, tmp_path):
    """A --changed subset or non-default budget must never rewrite the
    committed manifest (it would silently drop scenarios/edges)."""
    monkeypatch.setenv("DTPROTO_BUDGET", "2")
    out = io.StringIO()
    mpath = tmp_path / "m.json"
    ProtoManifest.load(DEFAULT_PROTO_MANIFEST_PATH).save(mpath)
    rc = run_proto(_args(update_baseline=True, manifest=str(mpath)), out)
    assert rc == 2
    assert "refusing" in out.getvalue()
