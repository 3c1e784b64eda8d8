"""Load plane (dtload) simulation tests: traffic-generator distribution
oracles, same-seed twin byte-identical determinism, a 3-worker e2e sim
proving KvIndexer overlap drives placement, the score_candidates pure
scoring seam, the injectable-clock seams the sim threads through the
observability/planner layers, and the serve_bench --sim mode."""

import json
import random
import subprocess
import sys
from pathlib import Path

import pytest

from dynamo_tpu.load.sim import (
    CELLS,
    LOAD_LEVELS,
    TOPOLOGIES,
    Topology,
    canonical_bytes,
    knee_level,
    run_cell,
)
from dynamo_tpu.load.traffic import (
    FAMILIES,
    arrival_histogram,
    generate,
    prefix_share,
    tenant_mass,
)
from dynamo_tpu.load.workers import LatencyModel

REPO = Path(__file__).resolve().parents[1]


# -------------------------------------------------------- traffic oracles


def test_generate_is_deterministic():
    a = generate(FAMILIES["agentic"], seed=7, rps=30, duration_s=10)
    b = generate(FAMILIES["agentic"], seed=7, rps=30, duration_s=10)
    assert a == b
    c = generate(FAMILIES["agentic"], seed=8, rps=30, duration_s=10)
    assert a != c


def test_zipf_tenant_skew():
    """The agentic family's Zipf skew concentrates mass on few tenants;
    the steady family (zipf_a=0) spreads uniformly."""
    ag = generate(FAMILIES["agentic"], seed=3, rps=40, duration_s=20)
    st = generate(FAMILIES["steady"], seed=3, rps=40, duration_s=20)
    assert tenant_mass(ag, 4) > 0.5      # 4 of 16 tenants dominate
    assert tenant_mass(st, 4) < 0.3      # 4 of 32 near-uniform tenants


def test_multi_turn_prompts_share_prefixes():
    """Multi-turn sessions grow by exact prefix extension, so a large
    fraction of an agentic trace's block hashes repeat — the resource
    KV routing exists to exploit.  Steady single-turn traffic shares
    nothing."""
    ag = generate(FAMILIES["agentic"], seed=3, rps=40, duration_s=20)
    st = generate(FAMILIES["steady"], seed=3, rps=40, duration_s=20)
    assert prefix_share(ag, 16) > 0.5
    assert prefix_share(st, 16) == 0.0
    # the exact-prefix property itself: turn k's tokens start with
    # turn k-1's tokens, per session
    by_session = {}
    for r in sorted(ag, key=lambda r: (r.session, r.turn)):
        prev = by_session.get(r.session)
        if prev is not None:
            assert r.token_ids[:len(prev)] == prev
        by_session[r.session] = r.token_ids


def test_burst_storms_shape_arrivals():
    """The burst family's storm + diurnal ramp gives a peaked arrival
    histogram; steady traffic is flat."""
    bu = generate(FAMILIES["burst"], seed=3, rps=40, duration_s=20)
    st = generate(FAMILIES["steady"], seed=3, rps=40, duration_s=20)

    def peak_over_mean(reqs):
        h = arrival_histogram(reqs, 20)
        return max(h) / (sum(h) / len(h))

    assert peak_over_mean(bu) > 1.5
    assert peak_over_mean(st) < 1.4


def test_arrivals_sorted_and_within_window():
    for fam in FAMILIES:
        reqs = generate(FAMILIES[fam], seed=1, rps=25, duration_s=8)
        arr = [r.arrival_s for r in reqs]
        assert arr == sorted(arr)
        assert all(0 <= a for a in arr)


# ---------------------------------------------------------- determinism


def test_same_seed_twin_runs_byte_identical():
    """The LD003 contract: two runs of a cell with the same seed
    produce byte-identical canonical results, across every family."""
    for fam, topo in [("agentic", "w4"), ("failure", "w16")]:
        a = run_cell(fam, topo, seed=11, level=1.0, target_requests=60)
        b = run_cell(fam, topo, seed=11, level=1.0, target_requests=60)
        assert canonical_bytes(a) == canonical_bytes(b), (fam, topo)


def test_different_seeds_differ():
    a = run_cell("agentic", "w4", seed=1, level=1.0, target_requests=60)
    b = run_cell("agentic", "w4", seed=2, level=1.0, target_requests=60)
    assert canonical_bytes(a) != canonical_bytes(b)


# ------------------------------------------------------------ e2e routing


def test_three_worker_sim_overlap_drives_placement():
    """3-worker e2e: the REAL KvIndexer's overlap scores must steer
    multi-turn follow-ups back to the worker holding the session's KV —
    each turn extends the previous prompt, so the indexer's
    longest-prefix match points at the warm worker."""
    t3 = Topology(name="w3", n_workers=3)
    res = run_cell("agentic", t3, seed=5, level=0.8, target_requests=120,
                   collect_decisions=True)
    dec = res["decisions"]
    multi = [d for d in dec if d["turn"] >= 1]
    assert len(multi) >= 10  # the trace really has follow-up turns
    with_overlap = sum(1 for d in multi if d["overlap_blocks"] > 0)
    assert with_overlap / len(multi) > 0.8
    prev_worker = {}
    same = total = 0
    for d in dec:
        if d["turn"] >= 1 and d["session"] in prev_worker:
            total += 1
            same += d["worker"] == prev_worker[d["session"]]
        prev_worker[d["session"]] = d["worker"]
    assert total and same / total > 0.7
    assert res["metrics"]["overlap_ratio"] > 0.3


def test_failure_storm_kills_and_recovers():
    res = run_cell("failure", "w4", seed=0, level=1.0, target_requests=80)
    c = res["census"]
    assert c.get("kills") == 1 and c.get("restores") == 1
    # the storm is survivable: most requests still complete
    m = res["metrics"]
    assert m["completed"] > 0.7 * m["requests"]


def test_disagg_topology_transfers_kv():
    res = run_cell("agentic", "w16", seed=0, level=1.0,
                   target_requests=60)
    assert res["census"].get("kv_transfers", 0) > 0
    assert res["census"].get("planner_ticks", 0) >= 1


def test_overload_level_sheds():
    """Level 2.0 on the single-worker cell is structurally past the
    knee: admission must shed rather than queue without bound."""
    res = run_cell("steady", "w1", seed=0, level=2.0, target_requests=160)
    assert res["metrics"]["shed_rate"] > 0.01


def test_cell_grid_covers_topologies_and_families():
    fams = {f for f, _ in CELLS}
    topos = {t for _, t in CELLS}
    assert fams == set(FAMILIES)
    assert topos == set(TOPOLOGIES)
    assert len(LOAD_LEVELS) >= 3


def test_knee_level_ranking():
    levels = {"0.5": {"ttft_p99_ms": 10, "shed_rate": 0.0},
              "1": {"ttft_p99_ms": 50, "shed_rate": 0.0},
              "2": {"ttft_p99_ms": 500, "shed_rate": 0.2}}
    assert knee_level(levels, sla_ttft_ms=100.0) == 2.0
    assert knee_level(levels, sla_ttft_ms=40.0) == 1.0
    assert knee_level(levels, sla_ttft_ms=1e9) is None or \
        knee_level(levels, sla_ttft_ms=1e9) == 2.0  # shed breaches


# --------------------------------------------------- score_candidates seam


def _sched(**kw):
    from dynamo_tpu.llm.kv_router.scheduler import (
        DefaultWorkerSelector,
        KvScheduler,
        WorkerMetrics,
    )

    s = KvScheduler(DefaultWorkerSelector(random.Random(0)),
                    block_size=16, **kw)
    s.update_worker(WorkerMetrics(1, request_active_slots=2,
                                  request_total_slots=8,
                                  kv_active_blocks=100,
                                  kv_total_blocks=1000))
    s.update_worker(WorkerMetrics(2, request_active_slots=6,
                                  request_total_slots=8,
                                  kv_active_blocks=900,
                                  kv_total_blocks=1000))
    return s


def test_score_candidates_breakdown_sums_to_logit():
    s = _sched(transfer_weight=1.0)
    scored = s.score_candidates({1: 3, 2: 6}, 128,
                                persist_overlaps={1: 5},
                                transfer_costs_s={2: 0.25})
    logits = [l for _, l, _ in scored]
    assert logits == sorted(logits, reverse=True)  # best first
    for wid, logit, breakdown in scored:
        assert set(breakdown) == {"overlap", "persist", "transfer",
                                  "kv_usage", "slot_usage"}
        assert logit == pytest.approx(sum(breakdown.values()))
    by = {w: b for w, _, b in scored}
    assert by[1]["persist"] > 0      # 2 extra persist blocks
    assert by[2]["transfer"] < 0     # costed hop
    assert by[2]["persist"] == 0.0


def test_score_candidates_is_pure_and_matches_schedule():
    """The seam mutates nothing and its top pick is the worker
    schedule() chooses for the same inputs (unique-logit case)."""
    s = _sched(transfer_weight=1.0)
    before = {w: m.request_active_slots for w, m in s.workers().items()}
    scored = s.score_candidates({1: 6}, 128, transfer_costs_s={2: 0.5})
    after = {w: m.request_active_slots for w, m in s.workers().items()}
    assert before == after             # pure: no optimistic slot bump
    assert s.drain_hit_events() == []  # pure: no hit events
    wid = s.schedule({1: 6}, 128, transfer_costs_s={2: 0.5})
    assert wid == scored[0][0]


def test_score_candidates_excludes_suspects():
    s = _sched()
    s.mark_suspect(1)
    assert [w for w, _, _ in s.score_candidates({}, 64)] == [2]


# ------------------------------------------------------------ clock seams


def test_transfer_cost_table_clock_injection():
    from dynamo_tpu.obs.costs import TransferCostTable

    t = [100.0]
    table = TransferCostTable(clock=lambda: t[0])
    table.record("a", "b", "ici", 1 << 20, 0.01)
    assert table.snapshot()[("a", "b", "ici")]["updated_at"] == 100.0
    t[0] = 250.0
    table.record("a", "b", "ici", 1 << 20, 0.01)
    assert table.snapshot()[("a", "b", "ici")]["updated_at"] == 250.0


def test_metrics_aggregator_clock_injection():
    from dynamo_tpu.llm.kv_router.metrics_aggregator import (
        KvMetricsAggregator,
    )
    from dynamo_tpu.llm.kv_router.scheduler import KvScheduler

    t = [42.0]
    sched = KvScheduler()
    agg = KvMetricsAggregator(None, sched, clock=lambda: t[0])
    agg._on_metrics("subj", json.dumps(
        {"worker_id": 7, "request_active_slots": 1,
         "request_total_slots": 8, "kv_active_blocks": 0,
         "kv_total_blocks": 1, "num_requests_waiting": 0}).encode())
    assert sched.workers()[7].updated_at == 42.0


def test_planner_loop_clock_injection():
    from dynamo_tpu.planner.core import PlannerLoop

    t = [5.0]
    loop = PlannerLoop(None, clock=lambda: t[0], stale_after_s=10.0)
    loop._on_metrics("subj", json.dumps(
        {"worker_id": 3, "request_active_slots": 1,
         "request_total_slots": 8}).encode())
    assert loop._metrics[3]["_rx"] == 5.0
    assert len(loop._samples([3])) == 1
    t[0] = 20.0   # past stale_after_s: the sample ages out
    assert len(loop._samples([3])) == 0


def test_step_timeline_clock_injection():
    from dynamo_tpu.obs.timeline import StepTimeline

    t = [0.0]
    tl = StepTimeline(clock=lambda: t[0])
    tl.begin("dispatch")
    t[0] = 0.010
    tl.enter("host_post")
    t[0] = 0.015
    tl.end()
    assert tl.busy_steps_total == 1
    assert tl.wall_s_total == pytest.approx(0.015)
    assert tl.phase_s_total["dispatch"] == pytest.approx(0.010)


# -------------------------------------------------------- latency model


def test_latency_model_from_perf_manifest():
    lat = LatencyModel.from_perf_manifest(scale=1.0)
    # per-token prefill and per-step decode come out in the tiny-rig's
    # microsecond range; the default production scale inflates both
    assert 0 < lat.prefill_ms_per_token < 1.0
    assert 0 < lat.decode_ms_per_step < 10.0
    assert lat.prefill_s(128) == pytest.approx(
        128 * lat.prefill_ms_per_token / 1e3)
    scaled = LatencyModel.from_perf_manifest(scale=100.0)
    assert scaled.prefill_s(128) == pytest.approx(100 * lat.prefill_s(128))
    # the router's Python cost is wall-clock-real and never scales
    assert scaled.router_s() == lat.router_s()


def test_latency_model_missing_manifest_falls_back(tmp_path):
    lat = LatencyModel.from_perf_manifest(tmp_path / "absent.json",
                                          scale=1.0)
    assert lat.prefill_ms_per_token > 0
    assert lat.decode_ms_per_step > 0


# ------------------------------------------------------- serve_bench --sim


def test_serve_bench_sim_mode():
    """--sim emits the same row/summary schema as the live sweep, off
    the virtual clock (no HTTP, no engine)."""
    out = subprocess.run(
        [sys.executable, "benchmarks/serve_bench.py", "--sim", "steady",
         "--sim-topology", "w1", "--sim-target", "40"],
        capture_output=True, text=True, timeout=240, cwd=str(REPO),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    summary = lines[-1]
    assert summary["metric"] == "serve_output_tok_s"
    assert summary["value"] > 0
    assert summary["sim_family"] == "steady"
    rows = lines[:-1]
    assert len(rows) == len(LOAD_LEVELS)
    for row in rows:
        assert {"concurrency", "requests", "output_tok_s", "ttft_p50_ms",
                "ttft_p95_ms", "itl_mean_ms"} <= set(row)
        assert row["ttft_p50_ms"] > 0
