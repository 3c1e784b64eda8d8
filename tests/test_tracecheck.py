"""Compile-plane static analysis (dttrace) tests: THE third tier-1 gate
(zero non-accepted findings over the registered entrypoints against the
committed trace manifest), the manifest contract (drift detection,
``--update`` justification carry-over, stable JSON), the donation /
dead-donation / upcast / HBM rules on synthetic entrypoints, and the
seeded runtime census — a real decode+prefill run proving each
EngineCore jitted impl compiles exactly once per declared signature
bucket (``jax.monitoring`` compile events + jit cache sizes).
"""

import argparse
import io
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import tracecheck as tc
from dynamo_tpu.analysis.tracecheck import (
    DEFAULT_MANIFEST_PATH,
    Entrypoint,
    Manifest,
    Signature,
    check_facts,
    collect_facts,
    enumerate_signatures,
    run_trace,
)

ROOT = Path(__file__).resolve().parents[1]


def _rules(findings):
    return {f.rule for f in findings}


# ----------------------------------------------------- synthetic registry ----


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _simple_ep(name="fake.step", axes=None, donate=(), fn=None,
               statics=None, **kw):
    """A tiny synthetic entrypoint over f(x, y) shapes — contract tests
    run on these instead of the real registry (which costs ~4s)."""
    fn = fn or (lambda x, y: (x + y, y * 2.0))
    axes = axes or {"n": [8, 16]}
    statics = statics or {}

    def build(n):
        return Signature(f"n={n}", (_sds((n,)), _sds((n,))), dict(statics))

    jit_fn = jax.jit(fn, donate_argnums=donate,
                     static_argnames=tuple(statics)) if donate else None
    raw = (lambda *a, **k: fn(*a)) if statics else fn
    return Entrypoint(name=name, axes=axes, build=build, jit_fn=jit_fn,
                      raw_fn=raw, donate_argnums=tuple(donate),
                      representatives=[dict(n=axes["n"][0])], **kw)


# ------------------------------------------------------------- the gate ----


@pytest.fixture(scope="module")
def real_facts():
    return collect_facts()


def test_trace_gate_zero_nonaccepted_findings(real_facts):
    """THE tier-1 compile-plane gate: the full entrypoint registry is
    clean against the committed trace manifest.  If this fails you
    either fix the regression (a retrace surface, a broken donation, a
    new f32 upcast, an over-budget config — preferred) or, for a
    justified by-design fact, re-snapshot with `dynamo-tpu lint --trace
    --update-baseline` and justify the new accepted entry."""
    manifest = Manifest.load(DEFAULT_MANIFEST_PATH)
    assert manifest.entrypoints, "trace manifest missing or empty"
    findings = check_facts(real_facts, manifest)
    fresh = manifest.filter(findings)
    assert not fresh, (
        "non-accepted compile-plane findings:\n  "
        + "\n  ".join(f.render() for f in fresh)
        + "\nFix the regression, or re-snapshot via `dynamo-tpu lint "
        "--trace --update-baseline` and add a justification "
        "(docs/static_analysis.md#compile-plane)."
    )


def test_manifest_accepted_entries_justified_and_live(real_facts):
    """Every accepted entry carries a real justification and still
    matches a current finding (no stale grandfathering) — shared
    contract in tests/manifest_hygiene.py."""
    from manifest_hygiene import assert_manifest_hygiene

    manifest = Manifest.load(DEFAULT_MANIFEST_PATH)
    assert_manifest_hygiene(manifest, check_facts(real_facts, manifest))


def test_manifest_header_records_cpu_derivation():
    """The committed header must say what the figures are: CPU-derived
    estimates, so perf-claiming PRs know to land numbers from a chip
    run (PERF.md)."""
    doc = json.loads(DEFAULT_MANIFEST_PATH.read_text())
    note = doc["header"]["note"]
    assert "CPU-derived" in note and "chip run" in note
    assert doc["header"]["hbm_budget"]["bytes"] > 0


def test_registry_covers_the_donated_engine_impls(real_facts):
    """The four donated EngineCore impls (plus the draft proposer and
    the donating block scatter) are registered, and every donated leaf
    is verified aliased in the lowered HLO."""
    donated = {n: f for n, f in real_facts.items()
               if f.get("donation") is not None}
    families = {n.split("[")[0] for n in donated}
    assert families >= {
        "engine.step", "engine.decode_multi", "engine.spec_verify",
        "engine.prefill_ragged", "engine.unified", "engine.draft_propose",
        "ops.scatter_blocks_inplace",
    }
    # the unified mixed dispatch is audited on BOTH cache layouts (the
    # QuantKvCache pytree doubles its donated leaf count)
    assert {"engine.unified[tiny-llama]",
            "engine.unified[tiny-llama-int8]"} <= set(donated)
    for name, f in donated.items():
        don = f["donation"]
        assert don["aliased_leaves"] == don["donated_leaves"], name
        assert not don["dead_leaves"], name


# ------------------------------------------------------- drift detection ----


def test_drift_added_and_removed_entrypoint():
    ep = _simple_ep()
    facts = collect_facts([ep])
    # empty manifest: the entrypoint is "added"
    f1 = check_facts(facts, Manifest())
    assert any(f.rule == "TR001" and f.key == "added" for f in f1)
    # manifest knows a second entrypoint that vanished: "removed"
    manifest = Manifest(entrypoints={**facts, "fake.gone[x]": {}})
    f2 = check_facts(facts, manifest)
    assert any(
        f.rule == "TR001" and f.key == "removed"
        and f.entrypoint == "fake.gone[x]" for f in f2
    )


def test_signature_drift_on_axis_change():
    ep = _simple_ep()
    manifest = Manifest(entrypoints=collect_facts([ep]))
    assert not check_facts(collect_facts([ep]), manifest)
    grown = _simple_ep(axes={"n": [8, 16, 32]})  # new bucket
    findings = check_facts(collect_facts([grown]), manifest)
    assert any(f.rule == "TR002" for f in findings)
    drift = next(f for f in findings if f.rule == "TR002")
    assert "axes" in drift.message  # the message names the changed axis


def test_unstable_trace_key_detected():
    """A static that hashes by identity (rebuilt per dispatch) makes the
    signature matrix unstable across enumerations — the compile-plane
    shape of a per-call retrace (cross-referenced by AST rule DT101)."""

    class Cfg:
        # repr differs per instance, like an id-keyed static — but via a
        # counter, not the heap address: the first enumeration's Cfg is
        # freed before the second is built, and allocator address reuse
        # would make object.__repr__ collide (order-dependent flake)
        _seq = 0

        def __repr__(self):
            Cfg._seq += 1
            return f"<Cfg #{Cfg._seq}>"

    def build(n):
        return Signature(f"n={n}", (_sds((n,)), _sds((n,))),
                         dict(cfg=Cfg()))

    ep = Entrypoint(name="fake.unstable", axes={"n": [8]}, build=build,
                    raw_fn=lambda x, y, **kw: x + y,
                    representatives=[dict(n=8)])
    findings = check_facts(collect_facts([ep]), Manifest())
    assert any(f.rule == "TR003" for f in findings)


# ------------------------------------------------------- donation audit ----


def test_donated_but_unaliased_is_found():
    """A donated buffer whose dtype changes through the computation
    cannot alias — TR004, the lowered-HLO complement of DT103."""
    def bad(cache, x):
        return (cache.astype(jnp.bfloat16) + x.astype(jnp.bfloat16),)

    def build(n):
        return Signature(f"n={n}", (_sds((n,)), _sds((n,))), {})

    ep = Entrypoint(name="fake.unaliased", axes={"n": [8]}, build=build,
                    jit_fn=jax.jit(bad, donate_argnums=(0,)), raw_fn=bad,
                    donate_argnums=(0,), representatives=[dict(n=8)])
    findings = check_facts(collect_facts([ep]), Manifest())
    assert any(f.rule == "TR004" for f in findings)


def test_dead_donation_is_found():
    def dead(cache, x):
        return (x * 2.0,)  # donated cache never read

    def build(n):
        return Signature(f"n={n}", (_sds((n,)), _sds((n,))), {})

    ep = Entrypoint(name="fake.dead", axes={"n": [8]}, build=build,
                    jit_fn=jax.jit(dead, donate_argnums=(0,)), raw_fn=dead,
                    donate_argnums=(0,), representatives=[dict(n=8)])
    findings = check_facts(collect_facts([ep]), Manifest())
    assert any(f.rule == "TR005" for f in findings)


def test_healthy_donation_is_clean():
    def good(cache, x):
        return x.sum(), cache.at[0].add(1.0)

    def build(n):
        return Signature(f"n={n}", (_sds((n,)), _sds((n,))), {})

    ep = Entrypoint(name="fake.good", axes={"n": [8]}, build=build,
                    jit_fn=jax.jit(good, donate_argnums=(0,)), raw_fn=good,
                    donate_argnums=(0,), representatives=[dict(n=8)])
    findings = check_facts(collect_facts([ep]), Manifest())
    assert not [f for f in findings if f.rule in ("TR004", "TR005")]


# -------------------------------------------------- upcasts + HBM budget ----


def test_new_upcast_site_fires_and_count_change_invalidates():
    def warm(x, y):
        return (x.astype(jnp.float32) + y.astype(jnp.float32)).sum(), y

    def build(n):
        return Signature(
            f"n={n}",
            (_sds((n,), jnp.bfloat16), _sds((n,), jnp.bfloat16)), {})

    ep = Entrypoint(name="fake.upcast", axes={"n": [8]}, build=build,
                    raw_fn=warm, representatives=[dict(n=8)],
                    upcast_min_elems=8)
    facts = collect_facts([ep])
    findings = check_facts(facts, Manifest(entrypoints=facts))
    up = [f for f in findings if f.rule == "TR006"]
    assert up and up[0].key.endswith("x2")
    # accepted at the current count: gate green
    manifest = Manifest(
        entrypoints=facts,
        accepted=[{**f.to_json(), "justification": "by design"}
                  for f in up],
    )
    assert not manifest.filter(check_facts(facts, manifest))
    # a count change at the same site class re-trips the gate
    mutated = json.loads(json.dumps(facts))
    mutated[ep.name]["upcasts"]["bfloat16->f32[r1]"] = 3
    fresh = manifest.filter(check_facts(mutated, manifest))
    assert any(f.rule == "TR006" and f.key.endswith("x3") for f in fresh)


def test_hbm_budget_finding():
    facts = {
        "fake.hbm": {
            "axes": {}, "n_signatures": 0, "signature_hash": "x",
            "stable": True, "traced": {}, "donation": None, "upcasts": {},
            "hbm": {
                "params_bytes": 9, "kv_bytes": 9,
                "peak_temp_decode_bytes": 9,
                "peak_temp_prefill_bytes_xla": 9,
                "total_bytes": 27, "budget_bytes": 20,
                "headroom_bytes": -7,
            },
        }
    }
    findings = check_facts(facts, Manifest(entrypoints=facts))
    assert any(f.rule == "TR007" for f in findings)


# --------------------------------------------------- update + CLI contract ----


def _args(**kw):
    base = dict(paths=None, fmt="text", select=None, baseline=None,
                no_baseline=False, update_baseline=False, root=None,
                project=False, trace=True, manifest=None)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.fixture()
def fake_registry(monkeypatch):
    """Route run_trace at a tiny synthetic registry so CLI contract
    tests don't pay the real ~4s fact collection."""
    ep = _simple_ep(
        name="fake.step",
        fn=lambda x, y: ((x.astype(jnp.float32) * y.astype(jnp.float32)
                          ).sum(), y),
    )
    ep.upcast_min_elems = 8

    def build(n):
        return Signature(
            f"n={n}",
            (_sds((n,), jnp.bfloat16), _sds((n,), jnp.bfloat16)), {})

    ep.build = build
    monkeypatch.setattr(tc, "build_registry", lambda: [ep])
    return ep


def test_update_roundtrip_carries_justifications(tmp_path, fake_registry):
    """finding -> exit 1 -> --update accepts it (TODO) -> justify ->
    second --update carries the justification by key -> gate green."""
    mpath = tmp_path / "manifest.json"
    args = _args(manifest=str(mpath))
    assert run_trace(args, out=io.StringIO()) == 1  # TR001 + TR006

    assert run_trace(_args(manifest=str(mpath), update_baseline=True),
                     out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert doc["entrypoints"]["fake.step"]["n_signatures"] == 2
    assert [e["justification"] for e in doc["accepted"]] == ["TODO: justify"]

    doc["accepted"][0]["justification"] = "kept: f32 reduction by design"
    mpath.write_text(json.dumps(doc))
    assert run_trace(args, out=io.StringIO()) == 0  # accepted + no drift

    assert run_trace(_args(manifest=str(mpath), update_baseline=True),
                     out=io.StringIO()) == 0
    doc = json.loads(mpath.read_text())
    assert [e["justification"] for e in doc["accepted"]] == [
        "kept: f32 reduction by design"
    ]


def test_json_output_stable_sorted(tmp_path, fake_registry):
    mpath = tmp_path / "manifest.json"
    outs = []
    for _ in range(2):
        out = io.StringIO()
        rc = run_trace(_args(manifest=str(mpath), fmt="json"), out=out)
        assert rc == 1
        outs.append(out.getvalue())
    assert outs[0] == outs[1], "trace JSON output must be stable"
    doc = json.loads(outs[0])
    keys = [(f["entrypoint"], f["rule"], f["key"]) for f in doc["findings"]]
    assert keys == sorted(keys)
    assert doc["total"] == len(doc["findings"]) + doc["accepted"]


def test_cli_routes_trace_flag(tmp_path, fake_registry):
    """`dynamo-tpu lint --trace` reaches the compile-plane pass through
    the shared lint CLI (run_lint routing)."""
    from dynamo_tpu.analysis.cli import run_lint

    out = io.StringIO()
    rc = run_lint(_args(manifest=str(tmp_path / "m.json")), out=out)
    assert rc == 1 and "TR001" in out.getvalue()


# --------------------------------------------------- seeded runtime census ----


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


def _drive(core, prompts, max_tokens=4):
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    outs = []
    for i, p in enumerate(prompts):
        core.submit(EngineRequest(
            f"r{i}", list(p), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=max_tokens), outs.append,
        ))
    for _ in range(64):
        if not core.step():
            break
    return outs


def test_seeded_run_compiles_once_per_bucket():
    """The acceptance proof for the census: a seeded decode+prefill run
    on a real EngineCore compiles each jitted impl exactly once per
    declared signature bucket, and an identical second run triggers ZERO
    further compile events (jax.monitoring) — no latent retrace."""

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore

    model, params = _runtime_model()
    rng = np.random.RandomState(0)
    p16 = list(rng.randint(1, 16, size=10))   # -> prefill bucket 16
    p32 = list(rng.randint(1, 16, size=20))   # -> prefill bucket 32

    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=8, num_blocks=32,
        prefill_buckets=[16, 32, 64], seed=0,
        # prefix reuse off: rerunning the same prompts must produce a
        # bit-identical dispatch stream (with reuse, the rerun's cached
        # prefixes select different — declared — prefix_blocks buckets)
        enable_prefix_reuse=False,
    ))
    _drive(core, [p16, p32])
    # legacy prefill: one executable per touched bucket, no more
    assert core._step_fn._cache_size() == 2
    # THE decode hot loop: exactly one executable, the one signature the
    # manifest declares
    assert core._multi_fn._cache_size() == 1

    compile_events = []

    def listener(name, **kw):
        if "compile" in name:
            compile_events.append(name)

    jax.monitoring.register_event_listener(listener)
    try:
        _drive(core, [p16, p32])  # identical seeded workload, fresh reqs
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert compile_events == [], (
        f"second identical run recompiled: {compile_events}"
    )
    assert core._step_fn._cache_size() == 2
    assert core._multi_fn._cache_size() == 1


def test_seeded_run_ragged_and_spec_once():
    """Same proof for the other two donated impls: the token-budget
    ragged prefill and the spec-verify dispatch each compile once, and
    the legacy per-request prefill never compiles when batching is on."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore

    model, params = _runtime_model()
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=8, num_blocks=32,
        prefill_buckets=[16, 32, 64], prefill_token_budget=32,
        spec_tokens=2, spec_ngram=1, seed=0,
    ))
    # both prompts fit one 32-token ragged dispatch; every vocab symbol
    # appears, so the 1-gram proposer always has a proposal and the spec
    # verify path engages deterministically
    prompts = [list(range(1, 11)), list(range(5, 16))]
    _drive(core, prompts, max_tokens=6)
    assert core.counts.prefill_dispatches_total >= 1
    assert core.counts.spec_steps >= 1, "spec verify never engaged"
    assert core._ragged_fn._cache_size() == 1
    assert core._spec_fn._cache_size() == 1
    assert core._step_fn._cache_size() == 0  # batching replaced it


def test_seeded_run_unified_once():
    """Census proof for the fifth donated impl: a seeded mixed
    prefill+decode workload compiles the unified dispatch exactly once
    for its single touched (t, r, pb) bucket, and an identical second
    run triggers ZERO further compile events — no latent retrace in the
    mixed hot loop."""

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    model, params = _runtime_model()

    def drive(core):
        outs = []
        # A reaches decode first; B arrives while A decodes, so the
        # turn that prefills B is a mixed one — the unified dispatch
        core.submit(EngineRequest(
            "a", list(range(1, 9)), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=8), outs.append))
        for _ in range(3):
            core.step()
        core.submit(EngineRequest(
            "b", list(range(2, 14)), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=4), outs.append))
        for _ in range(64):
            if not core.step():
                break
        return outs

    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=8, num_blocks=32,
        prefill_buckets=[16, 32, 64], prefill_token_budget=32,
        unified_token_dispatch=True, seed=0,
        # prefix reuse off: the rerun must replay a bit-identical
        # dispatch stream (cached prefixes would change the pb buckets)
        enable_prefix_reuse=False,
    ))
    drive(core)
    assert core.counts.unified_dispatches_total >= 1
    assert core._unified_fn._cache_size() == 1

    compile_events = []

    def listener(name, **kw):
        if "compile" in name:
            compile_events.append(name)

    jax.monitoring.register_event_listener(listener)
    try:
        drive(core)  # identical seeded workload, fresh requests
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert compile_events == [], (
        f"second identical run recompiled: {compile_events}"
    )
    assert core._unified_fn._cache_size() == 1


def test_runtime_buckets_are_declared_in_manifest():
    """Cross-plane check: the buckets the seeded runs exercise are
    inside the committed census axes for the matching entrypoints."""
    doc = json.loads(DEFAULT_MANIFEST_PATH.read_text())
    eps = doc["entrypoints"]
    step_axes = eps["engine.step[tiny-llama]"]["axes"]
    assert {16, 32}.issubset(set(step_axes["s_bucket"]))
    multi = eps["engine.decode_multi[tiny-llama]"]
    assert multi["n_signatures"] == 1 and multi["axes"] == {}
    ragged_axes = eps["engine.prefill_ragged[tiny-llama]"]["axes"]
    assert 32 in ragged_axes["t_bucket"]
    uni_axes = eps["engine.unified[tiny-llama]"]["axes"]
    assert 32 in uni_axes["t_bucket"]
    assert 2 in uni_axes["r_pad"]
