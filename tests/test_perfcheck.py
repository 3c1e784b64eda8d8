"""Perf-plane static analysis (dtperf) tests: THE fifth tier-1 gate
(zero non-accepted findings over the perf registry against the
committed perf manifest), the jaxpr FLOP/byte walker against
hand-computed oracles (matmul, attention, scan, cond, collectives),
the roofline bound classifier, the PF001-PF004 drift rules on the
committed ``tests/lint_fixtures/pf_*_facts.json`` fixture pair, the
manifest contract (``--update-baseline`` justification carry, stable
JSON, topology-constants re-trip), and the runtime reconciliation
loop — a seeded CPU engine run proving the predicted-vs-measured
gauge populates per dispatch kind and the Chrome trace of a busy step
carries the predicted envelope as a counter track.
"""

import argparse
import io
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import perfcheck as pc
from dynamo_tpu.analysis.perfcheck import (
    DEFAULT_MANIFEST_PATH,
    LATENCY_REL_TOL,
    TRANSCENDENTAL_WEIGHT,
    build_perf_registry,
    check_perf_facts,
    collect_perf_facts,
    estimate_callable,
    manifest_predictions,
    run_perf,
)
from dynamo_tpu.analysis.tracecheck import Entrypoint, Manifest, Signature
from dynamo_tpu.obs import topology

FIXTURES = Path(__file__).parent / "lint_fixtures"


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _est(fn, *args, **statics):
    return estimate_callable(fn, args, statics or None)


def _header(**kw):
    base = {"constants_version": topology.CONSTANTS_VERSION}
    base.update(kw)
    return base


def _load_facts(name):
    return json.loads((FIXTURES / name).read_text())


# ------------------------------------------------------------- the gate ----


@pytest.fixture(scope="module")
def real_facts():
    return collect_perf_facts()


def test_perf_gate_zero_nonaccepted_findings(real_facts):
    """THE tier-1 perf-plane gate: the full perf registry is clean
    against the committed perf manifest.  If this fails you either fix
    the hot-path regression (preferred) or, for an intended change,
    re-snapshot with `dynamo-tpu lint --perf --update-baseline` and
    justify any new collective entry."""
    manifest = Manifest.load(DEFAULT_MANIFEST_PATH)
    assert manifest.entrypoints, "perf manifest missing or empty"
    findings = check_perf_facts(real_facts, manifest)
    fresh = manifest.filter(findings)
    assert not fresh, (
        "non-accepted perf-plane findings:\n  "
        + "\n  ".join(f.render() for f in fresh)
        + "\nFix the regression, or re-snapshot via `dynamo-tpu lint "
        "--perf --update-baseline` and justify "
        "(docs/static_analysis.md#perf-plane)."
    )


def test_manifest_accepted_entries_justified_and_live(real_facts):
    from manifest_hygiene import assert_manifest_hygiene

    manifest = Manifest.load(DEFAULT_MANIFEST_PATH)
    assert_manifest_hygiene(
        manifest, check_perf_facts(real_facts, manifest))


def test_manifest_header_pins_constants_and_caveats():
    """The committed header records the topology-constants version (so
    a constants tweak re-trips PF001 explicitly), the tolerance bands,
    and the CPU-derivation caveat."""
    doc = json.loads(DEFAULT_MANIFEST_PATH.read_text())
    h = doc["header"]
    assert h["constants_version"] == topology.CONSTANTS_VERSION
    assert h["topology"] == topology.DEFAULT_TOPOLOGY
    assert h["tolerances"]["latency_rel"] == LATENCY_REL_TOL
    assert "CPU-derived" in h["note"]
    assert "predicted-vs-measured" in h["note"]


def test_registry_covers_engine_impls_and_perf_extras(real_facts):
    """All five EngineCore impls are priced, the ring-attention body
    contributes a live (costed) collective census, and the MLP
    reference row keeps a compute-bound entrypoint in the manifest."""
    families = {n.split("[")[0] for n in real_facts}
    assert families >= {
        "engine.step", "engine.decode_multi", "engine.spec_verify",
        "engine.prefill_ragged", "engine.unified", "engine.draft_propose",
        "roofline.mlp_reference",
    }
    ring = real_facts.get("ops.ring_attention[sp4]")
    assert ring is not None, "ring-attention collective site not priced"
    est = ring["signatures"]["s=128"]
    (ckey, c), = est["collectives"].items()
    assert ckey == "ppermute:sp" and c["axis_size"] == 4
    assert c["count"] == 12 and c["cost_us"] > 0
    mlp = real_facts["roofline.mlp_reference[llama3b-v5e]"]
    assert mlp["signatures"]["t=8192"]["predicted"]["bound"] == "compute"


def test_every_priced_signature_is_sane(real_facts):
    """No NaN/negative/absurd numbers anywhere in the committed matrix:
    every signature has positive bytes, non-negative flops, a finite
    positive predicted latency, and a consistent bound label."""
    for name, f in real_facts.items():
        for label, est in f["signatures"].items():
            where = f"{name}:{label}"
            assert est["bytes"] > 0, where
            assert est["flops"] >= 0, where
            assert est["flops"] == sum(est["flops_by_dtype"].values()), where
            p = est["predicted"]
            assert 0 < p["total_ms"] < 1e5, where
            expect = ("compute" if p["compute_ms"] >= p["memory_ms"]
                      else "bandwidth")
            assert p["bound"] == expect, where


# ------------------------------------------------------ jaxpr-walk oracle ----


def test_matmul_flops_and_bytes_exact():
    """Hand oracle: f32 [4,8]@[8,16] is exactly 2*64*8 = 1024 FLOPs and
    (32 + 128 + 64) * 4 = 896 HBM bytes."""
    est = _est(lambda a, b: a @ b, _sds((4, 8)), _sds((8, 16)))
    assert est["flops"] == 1024
    assert est["flops_by_dtype"] == {"float32": 1024}
    assert est["bytes"] == 896
    assert est["intensity"] == pytest.approx(1024 / 896, abs=1e-3)


def test_matmul_dtype_awareness():
    """bf16 operands land in the bf16 FLOP bucket (2x f32 peak on v5e),
    and the bf16 bytes are half the f32 bytes."""
    f32 = _est(lambda a, b: a @ b, _sds((64, 64)), _sds((64, 64)))
    bf16 = _est(lambda a, b: a @ b, _sds((64, 64), jnp.bfloat16),
                _sds((64, 64), jnp.bfloat16))
    assert list(bf16["flops_by_dtype"]) == ["bfloat16"]
    assert bf16["flops"] == f32["flops"] == 2 * 64 * 64 * 64
    assert bf16["bytes"] == f32["bytes"] // 2


def test_attention_flops_floor():
    """Tiny attention (scores @ softmax @ values): the two matmuls give
    an exact FLOP floor of 2*(s*s*d)*2; the softmax adds elementwise
    and reduction work on the [s, s] score matrix, bounded by a few
    weighted passes over it."""
    s, d = 16, 8

    def attn(q, k, v):
        scores = q @ k.T / jnp.sqrt(jnp.float32(d))
        return jax.nn.softmax(scores, axis=-1) @ v

    est = _est(attn, _sds((s, d)), _sds((s, d)), _sds((s, d)))
    floor = 2 * s * s * d * 2
    assert est["flops"] >= floor
    # softmax overhead: at most ~4 weighted elementwise/reduce passes
    assert est["flops"] <= floor + 4 * TRANSCENDENTAL_WEIGHT * s * s
    assert est["bytes"] > 0


def test_scan_multiplies_by_trip_count():
    def body(c, _):
        return c @ c, None

    def once(c):
        return body(c, None)[0]

    def scanned(c):
        out, _ = jax.lax.scan(body, c, None, length=4)
        return out

    one = _est(once, _sds((8, 8)))
    four = _est(scanned, _sds((8, 8)))
    assert four["flops"] == 4 * one["flops"]


def test_cond_takes_max_branch():
    big = lambda x: (x @ x).sum()
    small = lambda x: x.sum()

    def f(p, x):
        return jax.lax.cond(p, big, small, x)

    est = _est(f, _sds((), jnp.bool_), _sds((16, 16)))
    ref = _est(big, _sds((16, 16)))
    assert est["flops"] >= ref["flops"]  # priced the expensive branch
    assert est["flops"] < 2 * ref["flops"]  # not both branches summed


def test_free_and_transcendental_primitives():
    """Layout-only ops cost nothing; a transcendental costs
    TRANSCENDENTAL_WEIGHT per element vs 1 for plain elementwise."""
    free = _est(lambda x: x.reshape(4, 16)[None], _sds((8, 8)))
    assert free["flops"] == 0
    add = _est(lambda x, y: x + y, _sds((32,)), _sds((32,)))
    exp = _est(jnp.exp, _sds((32,)))
    assert add["flops"] == 32
    assert exp["flops"] == 32 * TRANSCENDENTAL_WEIGHT
    # fusion assumption: elementwise charges output bytes only
    assert add["bytes"] == 32 * 4


def test_scatter_priced_by_updates_not_combiner():
    """scatter-add charges the touched bytes (updates + indices, read
    and written) and one FLOP per update element — NOT the scalar
    combiner jaxpr it carries (the walk-order trap)."""
    n, k = 1024, 8

    def f(pool, idx, upd):
        return pool.at[idx].add(upd)

    est = _est(f, _sds((n,)), _sds((k,), jnp.int32), _sds((k,)))
    # one add per update element plus a few index-normalization ops on
    # the k indices (the .at[].add lowering clips/selects) — nowhere
    # near a per-pool-element combiner charge
    assert k <= est["flops"] <= 8 * k
    # operand pass-through aliases: bytes ~ 2*(updates+indices), far
    # below a full pool rewrite
    assert est["bytes"] < n * 4


def test_shard_map_collective_census_and_cost():
    """A psum inside shard_map over an abstract 4-way mesh produces a
    census entry with the right axis size and a nonzero analytic ring
    cost; the same code over a 1-way axis costs zero."""
    mesh = jax.sharding.AbstractMesh((4,), ("dp",))
    import functools

    from jax.sharding import PartitionSpec as P

    f = jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P(), check_vma=False)
    est = _est(f, _sds((64,)))
    (ckey, c), = est["collectives"].items()
    assert ckey == "psum:dp"
    assert c["axis_size"] == 4 and c["count"] == 1
    assert c["cost_us"] > 0
    assert est["predicted"]["collective_ms"] > 0
    # topology algebra: degenerate axis is free; ring cost grows with
    # the payload
    assert topology.collective_cost_s("psum", 1, 1 << 20) == 0.0
    assert topology.collective_cost_s("psum", 4, 1 << 24) > \
        topology.collective_cost_s("psum", 4, 1 << 20)


def test_roofline_bound_classification():
    """A big matmul lands compute-bound, an elementwise add lands
    bandwidth-bound, and total = max(compute, memory)."""
    mm = _est(lambda a, b: a @ b, _sds((2048, 2048)), _sds((2048, 2048)))
    assert mm["predicted"]["bound"] == "compute"
    assert mm["predicted"]["total_ms"] == mm["predicted"]["compute_ms"]
    ew = _est(lambda x, y: x + y, _sds((1 << 20,)), _sds((1 << 20,)))
    assert ew["predicted"]["bound"] == "bandwidth"
    assert ew["predicted"]["total_ms"] == ew["predicted"]["memory_ms"]


# ---------------------------------------------- drift rules (fixture pair) ----


def test_fixture_baseline_is_clean():
    """Good case: facts identical to the committed baseline produce
    zero findings (no intrinsic census entries in the baseline pair)."""
    base = _load_facts("pf_baseline_facts.json")
    manifest = Manifest(entrypoints=base, header=_header())
    assert check_perf_facts(base, manifest) == []


def test_fixture_regression_fires_pf001_pf002_pf003_pf004():
    """Bad case: the regressed fixture (latency x3, bytes x2 on the
    bandwidth-bound decode; intensity halved on the compute-bound MLP;
    a new psum) demonstrably fails every rule."""
    base = _load_facts("pf_baseline_facts.json")
    bad = _load_facts("pf_regressed_facts.json")
    manifest = Manifest(entrypoints=base, header=_header())
    findings = check_perf_facts(bad, manifest)
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule, []).append(f)
    assert {"PF001", "PF002", "PF003", "PF004"} <= set(by_rule)
    pf001 = by_rule["PF001"][0]
    assert pf001.entrypoint == "fix.decode[tiny]" and pf001.key == "k=1"
    assert by_rule["PF002"][0].key == "k=1:psum:dpx2"
    assert by_rule["PF003"][0].entrypoint == "fix.mlp[tiny]"
    assert by_rule["PF004"][0].entrypoint == "fix.decode[tiny]"


def test_small_drift_within_tolerance_is_clean():
    base = _load_facts("pf_baseline_facts.json")
    wob = json.loads(json.dumps(base))
    sig = wob["fix.decode[tiny]"]["signatures"]["k=1"]
    sig["predicted"]["total_ms"] *= 1 + LATENCY_REL_TOL * 0.5
    sig["bytes"] = int(sig["bytes"] * 1.02)
    manifest = Manifest(entrypoints=base, header=_header())
    assert check_perf_facts(wob, manifest) == []


def test_added_and_removed_entrypoints():
    base = _load_facts("pf_baseline_facts.json")
    manifest = Manifest(entrypoints=base, header=_header())
    only_decode = {"fix.decode[tiny]": base["fix.decode[tiny]"]}
    f1 = check_perf_facts(only_decode, manifest)
    assert any(f.rule == "PF001" and f.key == "removed"
               and f.entrypoint == "fix.mlp[tiny]" for f in f1)
    grown = dict(base)
    grown["fix.new[tiny]"] = base["fix.decode[tiny]"]
    f2 = check_perf_facts(grown, manifest)
    assert any(f.rule == "PF001" and f.key == "added"
               and f.entrypoint == "fix.new[tiny]" for f in f2)


def test_constants_version_mismatch_retrips_pf001():
    """A topology-constants tweak moves every predicted number at once;
    the pinned header version makes that an explicit finding instead of
    a silent baseline shift.  An empty manifest (first snapshot) is
    exempt."""
    base = _load_facts("pf_baseline_facts.json")
    stale = Manifest(entrypoints=base,
                     header=_header(constants_version="v5e-1999.01.0"))
    findings = check_perf_facts(base, stale)
    assert any(f.rule == "PF001" and f.key == "constants"
               for f in findings)
    assert not check_perf_facts({}, Manifest())


def test_pf002_acceptance_is_count_keyed():
    """An accepted census entry covers exactly its op x axis x count;
    a count change at the same site re-trips the gate (like TR006)."""
    bad = _load_facts("pf_regressed_facts.json")
    manifest = Manifest(entrypoints=bad, header=_header(), accepted=[{
        "entrypoint": "fix.decode[tiny]", "rule": "PF002",
        "key": "k=1:psum:dpx2", "justification": "by design",
    }])
    assert not manifest.filter(check_perf_facts(bad, manifest))
    mutated = json.loads(json.dumps(bad))
    census = mutated["fix.decode[tiny]"]["signatures"]["k=1"]["collectives"]
    census["psum:dp"]["count"] = 3
    fresh = manifest.filter(check_perf_facts(mutated, manifest))
    assert any(f.rule == "PF002" and f.key.endswith("x3") for f in fresh)


# --------------------------------------------------- update + CLI contract ----


def _args(**kw):
    base = dict(paths=None, fmt="text", select=None, baseline=None,
                no_baseline=False, update_baseline=False, root=None,
                project=False, trace=False, wire=False, perf=True,
                manifest=None)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.fixture()
def fake_registry(monkeypatch):
    """Route run_perf at a tiny synthetic registry (one matmul with a
    psum inside shard_map) so the CLI contract tests don't pay the real
    multi-second fact collection."""
    import functools

    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.AbstractMesh((2,), ("dp",))
    f = jax.shard_map(lambda x, w: jax.lax.psum(x @ w, "dp"), mesh=mesh,
                      in_specs=(P(None, "dp"), P("dp", None)), out_specs=P(),
                      check_vma=False)

    def build(n):
        return Signature(f"n={n}", (_sds((n, 2 * n)), _sds((2 * n, n))),
                         {})

    ep = Entrypoint(name="fake.psum_mm", axes={"n": [8]}, build=build,
                    raw_fn=f, representatives=[dict(n=8)])
    monkeypatch.setattr(pc, "build_perf_registry", lambda: [ep])
    return ep


def test_update_roundtrip_carries_justifications(tmp_path, fake_registry):
    """finding -> exit 1 -> --update accepts the census (TODO) ->
    justify -> second --update carries the justification by key ->
    gate green; the header pins the constants version."""
    mpath = tmp_path / "manifest.json"
    args = _args(manifest=str(mpath))
    assert run_perf(args, out=io.StringIO()) == 1  # PF001 added + PF002

    assert run_perf(_args(manifest=str(mpath), update_baseline=True),
                    out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert doc["header"]["constants_version"] == topology.CONSTANTS_VERSION
    assert "fake.psum_mm" in doc["entrypoints"]
    assert [e["justification"] for e in doc["accepted"]] == ["TODO: justify"]
    assert doc["accepted"][0]["rule"] == "PF002"

    doc["accepted"][0]["justification"] = "kept: dp-reduced matmul"
    mpath.write_text(json.dumps(doc))
    assert run_perf(args, out=io.StringIO()) == 0

    assert run_perf(_args(manifest=str(mpath), update_baseline=True),
                    out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert [e["justification"] for e in doc["accepted"]] == [
        "kept: dp-reduced matmul"
    ]


def test_json_output_stable_sorted(tmp_path, fake_registry):
    mpath = tmp_path / "manifest.json"
    outs = []
    for _ in range(2):
        out = io.StringIO()
        rc = run_perf(_args(manifest=str(mpath), fmt="json"), out=out)
        assert rc == 1
        outs.append(out.getvalue())
    assert outs[0] == outs[1], "perf JSON output must be stable"
    doc = json.loads(outs[0])
    keys = [(f["entrypoint"], f["rule"], f["key"]) for f in doc["findings"]]
    assert keys == sorted(keys)
    assert doc["total"] == len(doc["findings"]) + doc["accepted"]


def test_cli_routes_perf_flag(tmp_path, fake_registry):
    """`dynamo-tpu lint --perf` reaches the perf-plane pass through the
    shared lint CLI (run_lint routing)."""
    from dynamo_tpu.analysis.cli import run_lint

    out = io.StringIO()
    rc = run_lint(_args(manifest=str(tmp_path / "m.json")), out=out)
    assert rc == 1 and "PF00" in out.getvalue()


def test_manifest_predictions_rows():
    """The /metrics export path: flat rows straight from the committed
    JSON, split into entrypoint/config, no jax involved."""
    rows = manifest_predictions(DEFAULT_MANIFEST_PATH)
    assert rows, "committed manifest has no prediction rows"
    by_ep = {(r["entrypoint"], r["config"], r["signature"]): r
             for r in rows}
    key = ("roofline.mlp_reference", "llama3b-v5e", "t=8192")
    assert key in by_ep and by_ep[key]["bound"] == "compute"
    for r in rows:
        assert r["predicted_ms"] > 0
        assert r["bound"] in ("compute", "bandwidth")


# ------------------------------------------------- runtime reconciliation ----


def _runtime_model():
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    cfg = ModelConfig(
        vocab_size=16, hidden_size=16, intermediate_size=32, num_layers=1,
        num_heads=2, num_kv_heads=1, head_dim=8,
        max_position_embeddings=128, dtype="float32",
    )
    model = LlamaModel(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_seeded_run_populates_predicted_vs_measured_gauge():
    """The loop-closing acceptance: a seeded CPU engine run leaves
    perf_model.reconcile() populated — measured dispatch ms per kind
    from the step timeline AND a lazily-traced roofline prediction for
    each offered kind — and the Chrome trace of a busy step carries the
    predicted envelope as a dtperf counter track."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
    from dynamo_tpu.obs import tracing
    from dynamo_tpu.obs.export import chrome_trace
    from dynamo_tpu.obs.perfmodel import perf_model
    from dynamo_tpu.obs.timeline import step_timeline
    from dynamo_tpu.obs.metric_names import PerfMetric as PM

    was = tracing.enabled()
    tracing.enable(True)
    tracing.collector.reset()
    step_timeline.reset()
    perf_model.reset()
    try:
        model, params = _runtime_model()
        core = EngineCore(model, params, EngineConfig(
            max_batch_size=2, max_model_len=64, block_size=8,
            num_blocks=32, prefill_buckets=[16, 32, 64], seed=0,
        ))
        rng = np.random.RandomState(0)
        outs = []
        for i in range(2):
            core.submit(EngineRequest(
                f"r{i}", list(rng.randint(1, 16, size=10)),
                SamplingOptions(temperature=0.0),
                StopConditions(max_tokens=6), outs.append,
            ))
        for _ in range(64):
            if not core.step():
                break
        assert outs, "engine produced no output"

        rows = {r["kind"]: r for r in perf_model.reconcile()}
        assert rows, "no reconciliation rows after a busy run"
        # the decode hot loop must be reconciled end to end: measured
        # seconds from the timeline, predicted ms from the lazy trace
        decode = rows.get("decode_multi") or rows.get("step")
        assert decode is not None
        assert decode["dispatches"] >= 1
        assert decode["measured_ms"] and decode["measured_ms"] > 0
        assert decode["predicted_ms"] and decode["predicted_ms"] > 0
        assert decode["error_ratio"] and decode["error_ratio"] > 0
        # every offered kind got a usable prediction (a None here means
        # the offered signature failed to trace — a perfmodel bug)
        for kind in perf_model.kinds():
            assert perf_model.predicted_ms(kind) is not None, kind

        # Chrome export: busy engine.step spans exist and the counter
        # track carries the predicted envelope alongside the measured
        steps = [s for s in list(tracing.collector.spans)
                 if s["name"] == "engine.step"]
        assert steps, "no engine.step spans emitted under tracing"
        assert any("predicted_dispatch_ms" in (s.get("attrs") or {})
                   for s in steps)
        doc = chrome_trace(steps)
        counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert counters and counters[0]["cat"] == "dtperf"
        assert any("predicted" in e["args"] and "measured" in e["args"]
                   for e in counters)
    finally:
        tracing.enable(was)
        tracing.collector.reset()
        step_timeline.reset()
        perf_model.reset()


def test_metrics_render_exports_perf_gauges():
    """/metrics exposes both halves: the static per-(entrypoint,
    config) predicted_step_ms rows from the committed manifest and the
    runtime per-kind predicted/measured/error gauges."""
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.obs.metric_names import PerfMetric as PM
    from dynamo_tpu.obs.perfmodel import perf_model
    from dynamo_tpu.obs.timeline import step_timeline

    step_timeline.reset()
    perf_model.reset()
    try:
        f = jax.jit(lambda x: x @ x)
        x = jnp.ones((32, 32), jnp.float32)
        # the timeline is process-global with engine-thread writers; a
        # straggling engine thread from an earlier test calling
        # begin()/end() between our calls silently swallows the
        # dispatch sample — retry until our mark lands
        for _ in range(5):
            step_timeline.begin("upload")
            step_timeline.enter("dispatch", kind="step")
            perf_model.offer("step", f, (x,))
            f(x)
            step_timeline.end()
            if step_timeline.dispatch_kind_n.get("step"):
                break
        text = Metrics().render()
        assert f'{PM.PREDICTED_STEP_MS}{{entrypoint="' in text
        assert 'config="llama3b-v5e"' in text
        assert f'{PM.PREDICTED_DISPATCH_MS}{{kind="step"}}' in text
        assert f'{PM.MEASURED_DISPATCH_MS}{{kind="step"}}' in text
        assert f'{PM.MODEL_ERROR_RATIO}{{kind="step"}}' in text
    finally:
        step_timeline.reset()
        perf_model.reset()
