"""Kernel-plane static analysis (dtkern): the Pallas audit.

Eight planes audit source, traces, wire contracts, priced jaxprs,
placement, protocol state machines and the scaled control plane — and
none of them sees the kernels.  `dynamo_tpu/ops/pallas/` (flash decode,
flash prefill + ragged variant, dequant-in-kernel int8 matmul) is where
ROADMAP item 2's unified-kernel rewrite will land, and until this plane
existed it was audited by nothing: VMEM footprints were a docstring
claim, index maps were reviewed by eye, padded-lane masking was
spot-tested at two geometries, and dtperf priced the ops via their XLA
fallback jaxprs with a written caveat.

The plane audits every `pallas_call` site registered in
``ops/pallas/registry.py`` across that registry's geometry matrix
(decode bf16/int8, multi-query decode, prefill, ragged prefill
bf16/int8 with adversarial rows — empty, 1-token with non-block-aligned
starts, non-block-divisible lengths, max-block — int8 matmul, plus
serving-scale spec-only shapes), entirely on CPU.  A `pallas_call` spy
captures grid, BlockSpecs, scratch and operand avals at call time; the
small geometries then execute in interpret mode against the pure-XLA
oracles, the serving-scale ones are shape-traced only
(``jax.eval_shape``).  Four audit families:

- **VMEM budget (KN001)**: per-grid-step resident bytes = blocked
  operand/output block shapes x dtypes x the pipeline double-buffering
  multiplier + VMEM scratch, against the per-core v5e budget
  (``registry.VMEM_BUDGET_BYTES``).  Snapshotted per (kernel,
  geometry), so "128 rows/chunk fits VMEM at S=2048" is a checked fact,
  not a comment.
- **index-map audit (KN002/KN003)**: every BlockSpec index map is
  evaluated concretely over the full grid.  A block index outside the
  operand's block range is KN002 (out-of-bounds touch).  Two grid steps
  mapping to the same OUTPUT block are only sound when the revisits are
  consecutive in sequential grid order (the TPU revisit-accumulate
  pattern, e.g. the matmul K axis); non-consecutive revisits are a
  write race under arbitrary grid order — KN003.
- **padding oracles (KN004)**: interpret-mode differential runs on the
  adversarial geometries vs the pure-XLA oracle, with NaN-poisoned
  padding lanes and NaN-poisoned out-of-``seq_len`` cache blocks (f32
  scale lanes for the int8 cache — int8 data can't hold a NaN).  A
  canary reaching a live output lane, or a live-lane mismatch beyond
  the case tolerance, is a padding leak.  This is the correctness
  harness the item-2 unified kernel will be built against.
- **kernel pricing (KN005)**: the registry's analytic cost model
  (HBM-DMA bytes, FLOPs, transcendentals, arithmetic intensity) per
  (kernel, geometry), exported to dtperf — perfcheck attaches these to
  the entrypoints that dispatch Pallas kernels on TPU, replacing the
  XLA-fallback pricing caveat for those ops.  Drift vs the committed
  manifest (pricing, VMEM, grid) is KN005.

Cross-plane tripwires (KN006): the registry's kernel census records
that decode and ragged-prefill attention are SEPARATE kernels while the
unified kernel (ROADMAP item 2, *Ragged Paged Attention*, arxiv
2604.15464) is a placeholder — a permanent finding whose accepted
manifest entry cites item 2, so landing the unified kernel re-trips
this gate and forces the acceptance (and the shard plane's fallback
entries) to be retired deliberately.  The same census pins the shard
manifest's accepted SH002 fallback-gather counts and requires every
registered kernel to carry a bench probe.

Facts commit to ``analysis/kern_manifest.json`` under the shared
justification / ``--update-baseline`` contract (tracecheck's
``Manifest``).  A nightly ``kern-fuzz`` mode
(``DTKERN_BUDGET``/``DTKERN_SEED_BASE``) sweeps seeded random ragged
geometries through the KN004 oracle; failures print ``dtk1.`` replay
tokens that re-run one geometry exactly.

Interpret-mode caveats (recorded in the manifest header): interpret
mode checks semantics, not Mosaic lowering — a kernel can pass here and
still fail to compile on hardware (probe_kernels.py owns that half);
the manual DMA double-buffering runs serially in interpret mode, so
overlap bugs (wait-before-start) surface as wrong values, not hangs.
"""

from __future__ import annotations

import base64
import itertools
import json
import math
import os
import zlib
from pathlib import Path

from dynamo_tpu.analysis.tracecheck import Manifest, TraceFinding

__all__ = [
    "DEFAULT_MANIFEST_PATH",
    "KERN_RULES",
    "check_kern_facts",
    "collect_kern_facts",
    "decode_token",
    "encode_token",
    "run_kern",
]

DEFAULT_MANIFEST_PATH = Path(__file__).parent / "kern_manifest.json"

_TOKEN_PREFIX = "dtk1."

KERN_RULES = {
    "KN001": ("vmem-over-budget",
              "per-grid-step resident bytes (blocked operands x "
              "double-buffering + VMEM scratch) exceed the per-core "
              "VMEM budget"),
    "KN002": ("index-map-out-of-bounds",
              "a BlockSpec index map touches a block outside the "
              "operand's block range at some grid step"),
    "KN003": ("output-aliasing-race",
              "two non-consecutive grid steps map to the same output "
              "block — a write race under arbitrary grid order"),
    "KN004": ("padding-leak",
              "a NaN canary planted in padding lanes / dead cache "
              "slots reached a live output lane, or live lanes diverge "
              "from the pure-XLA oracle beyond tolerance"),
    "KN005": ("kernel-drift",
              "kernel pricing / VMEM / grid facts drifted vs the "
              "committed kern manifest (re-snapshot deliberately with "
              "--update-baseline)"),
    "KN006": ("census-drift",
              "kernel census out of sync: the two-kernel decode/ragged "
              "split (ROADMAP item 2 tripwire), the shard-plane "
              "fallback acceptances, or a registered kernel without a "
              "bench probe"),
}

_MANIFEST_NOTE = (
    "CPU-derived Pallas kernel facts over the registry geometry matrix "
    "(ops/pallas/registry.py).  VMEM/index-map/pricing facts come from "
    "a pallas_call capture (spec math, no execution); KN004 canaries "
    "execute the small geometries in INTERPRET mode against the "
    "pure-XLA oracles with NaN-poisoned padding, so they check "
    "semantics, not Mosaic lowering (probe_kernels.py owns on-TPU "
    "compilation).  Serving-scale geometries are shape-traced only.  "
    "The accepted two-kernel-split entry pins ROADMAP item 2: landing "
    "the unified ragged kernel (arxiv 2604.15464) re-trips KN006 and "
    "forces this acceptance and the shard-plane fallback entries to be "
    "retired together."
)

# KN005 pricing drift tolerance: the model is deterministic integer
# math, so any change is a real change — exact match required.


def _kern_header() -> dict:
    from dynamo_tpu.ops.pallas.registry import (
        V5E_VMEM_BYTES,
        VMEM_BUDGET_BYTES,
    )

    return {
        "note": _MANIFEST_NOTE,
        "vmem_budget": {
            "chip": "v5e",
            "vmem_bytes": int(V5E_VMEM_BYTES),
            "budget_bytes": int(VMEM_BUDGET_BYTES),
        },
    }


# ------------------------------------------------------------ replay token


def encode_token(payload: dict) -> str:
    raw = json.dumps(payload, sort_keys=True,
                     separators=(",", ":")).encode()
    return _TOKEN_PREFIX + base64.urlsafe_b64encode(
        zlib.compress(raw, 9)).decode().rstrip("=")


def decode_token(token: str) -> dict:
    if not token.startswith(_TOKEN_PREFIX):
        raise ValueError(f"not a dtkern replay token: {token[:16]!r}")
    body = token[len(_TOKEN_PREFIX):]
    body += "=" * (-len(body) % 4)
    return json.loads(zlib.decompress(base64.urlsafe_b64decode(body)))


def _budget_env() -> tuple[int, int, bool]:
    """(budget, seed_base, pinned).  The pinned default run (budget 1,
    seed base 0) audits exactly the committed geometry matrix; the
    nightly fuzz job raises DTKERN_BUDGET and derives DTKERN_SEED_BASE
    from the date, adding seeded random ragged geometries that are
    canary-checked but never enter the manifest."""
    budget = max(1, int(os.environ.get("DTKERN_BUDGET", "1") or 1))
    seed_base = int(os.environ.get("DTKERN_SEED_BASE", "0") or 0)
    return budget, seed_base, budget == 1 and seed_base == 0


# ----------------------------------------------------------- VMEM facts ----


def _space_name(spec_or_ref) -> str:
    ms = getattr(spec_or_ref, "memory_space", None)
    return str(getattr(ms, "name", ms) or "").lower()


def _itemsize(dtype) -> int:
    import jax.numpy as jnp

    return jnp.dtype(dtype).itemsize


def _blocked_entries(rec: dict) -> list[dict]:
    """One entry per pallas operand/output: label, backing array shape,
    block shape (None for un-blocked ANY-space residents) and the
    per-step VMEM block bytes."""
    nsp = rec["num_scalar_prefetch"]
    entries = []
    pairs = (
        [(f"in{i}", spec, aval) for i, (spec, aval) in
         enumerate(zip(rec["in_specs"], rec["operands"][nsp:]))]
        + [(f"out{i}", spec, aval) for i, (spec, aval) in
           enumerate(zip(rec["out_specs"], rec["out_shapes"]))]
    )
    for label, spec, (shape, dtype) in pairs:
        block = getattr(spec, "block_shape", None)
        space = _space_name(spec)
        if block is None or "any" in space:
            entries.append({
                "operand": label, "shape": list(shape), "dtype": dtype,
                "block": None, "block_bytes": 0, "space": space or "any",
                "index_map": None,
            })
            continue
        # a squeezed dimension (None) is a block of one
        block = [1 if x is None else int(x) for x in block]
        nbytes = _itemsize(dtype)
        for x in block:
            nbytes *= x
        entries.append({
            "operand": label, "shape": list(shape), "dtype": dtype,
            "block": block, "block_bytes": int(nbytes),
            "space": space or "vmem",
            "index_map": getattr(spec, "index_map", None),
        })
    return entries


def _scratch_bytes(rec: dict) -> int:
    total = 0
    for ref in rec["scratch"]:
        if "sem" in _space_name(ref):
            continue  # semaphores don't occupy VMEM data space
        nbytes = _itemsize(ref.dtype)
        for x in ref.shape:
            nbytes *= int(x)
        total += nbytes
    return total


def _vmem_facts(rec: dict) -> dict:
    from dynamo_tpu.ops.pallas.registry import (
        DOUBLE_BUFFER,
        VMEM_BUDGET_BYTES,
    )

    entries = _blocked_entries(rec)
    blocked = sum(e["block_bytes"] for e in entries)
    scratch = _scratch_bytes(rec)
    return {
        "blocked_bytes": int(blocked),
        "scratch_bytes": int(scratch),
        "resident_bytes": int(blocked * DOUBLE_BUFFER + scratch),
        "budget_bytes": int(VMEM_BUDGET_BYTES),
        "blocks": [
            {k: e[k] for k in
             ("operand", "shape", "dtype", "block", "block_bytes",
              "space")}
            for e in entries
        ],
    }


# ------------------------------------------------------ index-map facts ----

_MAX_OOB_PER_OPERAND = 4  # cap the recorded offenders per operand


def _index_map_facts(rec: dict) -> dict:
    """Evaluate every blocked index map over the full grid.  Grid steps
    enumerate in sequential TPU order (row-major, last axis fastest) —
    the order the race check's "consecutive revisits" notion refers
    to."""
    import numpy as np

    grid = rec["grid"]
    steps = list(itertools.product(*[range(int(n)) for n in grid]))
    # an index map sees the prefetched scalars behind the grid indices, as
    # under pallas: the audit holds them at zero
    prefetch = [np.zeros(shape, dtype) for shape, dtype in
                rec["operands"][:rec["num_scalar_prefetch"]]]
    oob: list[dict] = []
    races: list[dict] = []
    max_revisit = 1
    for e in _blocked_entries(rec):
        im, block = e["index_map"], e["block"]
        if im is None or block is None:
            continue
        nblocks = [
            max(1, -(-int(dim) // int(bd)))
            for dim, bd in zip(e["shape"], block)
        ]
        seen: dict[tuple, list[int]] = {}
        n_oob = 0
        for pos, step in enumerate(steps):
            idx = tuple(int(x) for x in im(*step, *prefetch))
            if len(idx) != len(nblocks) or any(
                    not 0 <= i < n for i, n in zip(idx, nblocks)):
                if n_oob < _MAX_OOB_PER_OPERAND:
                    oob.append({
                        "operand": e["operand"],
                        "step": list(step), "block_index": list(idx),
                        "block_range": nblocks,
                    })
                n_oob += 1
                continue
            if e["operand"].startswith("out"):
                seen.setdefault(idx, []).append(pos)
        for idx, positions in sorted(seen.items()):
            if len(positions) <= 1:
                continue
            max_revisit = max(max_revisit, len(positions))
            consecutive = positions[-1] - positions[0] == \
                len(positions) - 1
            if not consecutive:
                races.append({
                    "operand": e["operand"], "block_index": list(idx),
                    "steps": [list(steps[p]) for p in positions[:4]],
                    "revisits": len(positions),
                })
    return {"oob": oob, "races": races, "max_revisit": int(max_revisit)}


# --------------------------------------------------------- canary facts ----


def _canary_facts(case: dict, inp: dict, clean_out) -> dict:
    """The KN004 differential: clean interpret output vs the pure-XLA
    oracle on live lanes (+ exact-zero claims), then a NaN-poisoned run
    whose live lanes must stay finite AND on-oracle."""
    import numpy as np

    ref, live, zero = case["oracle"](inp)
    out = np.asarray(clean_out, np.float32)
    err = float(np.abs(out - ref)[live].max()) if live.any() else 0.0
    zero_ok = bool((out[zero] == 0).all()) if zero.any() else True
    pout = np.asarray(case["run"](inp, poisoned=True), np.float32)
    nonfinite = int((~np.isfinite(pout[live])).sum())
    perr = (float(np.abs(pout - ref)[live].max())
            if live.any() and nonfinite == 0 else float("inf")
            if nonfinite else 0.0)
    return {
        "ran": True,
        "atol": float(case["atol"]),
        "max_abs_err": round(err, 9),
        "poisoned_max_abs_err":
            round(perr, 9) if math.isfinite(perr) else "inf",
        "nonfinite_live": nonfinite,
        "zero_rows_ok": zero_ok,
        "live_lanes": int(live.sum()),
    }


def _canary_failed(canary: dict) -> bool:
    if not canary.get("ran"):
        return False
    perr = canary["poisoned_max_abs_err"]
    perr = float("inf") if perr == "inf" else float(perr)
    return (
        canary["nonfinite_live"] > 0
        or canary["max_abs_err"] > canary["atol"]
        or perr > canary["atol"]
        or not canary["zero_rows_ok"]
    )


# -------------------------------------------------------------- collect ----


def _case_facts(case: dict) -> dict:
    from dynamo_tpu.ops.pallas.registry import capture_pallas_calls

    inp = case["build"]()
    records: list[dict] = []
    with capture_pallas_calls(records):
        out = case["run"](inp, poisoned=False)
    assert len(records) == 1, (case["name"], len(records))
    rec = records[0]
    canary = (_canary_facts(case, inp, out)
              if case["mode"] == "interpret" else {"ran": False})
    return {
        "kernel": case["kernel"],
        "geometry": case["name"],
        "mode": case["mode"],
        "grid": [int(x) for x in rec["grid"]],
        "vmem": _vmem_facts(rec),
        "index_map": _index_map_facts(rec),
        "canary": canary,
        "pricing": case["pricing"](),
    }


def _shard_accepted_sh002(path: Path | None = None) -> dict:
    """The SH002 entries the shard manifest currently accepts, as
    {entrypoint: {collective: count}} — read at collect time so the
    KN006 sync check is against the file as committed."""
    from dynamo_tpu.analysis import shardcheck

    path = path or shardcheck.DEFAULT_MANIFEST_PATH
    try:
        doc = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return {}
    out: dict[str, dict] = {}
    for a in doc.get("accepted", []):
        if a.get("rule") != "SH002":
            continue
        op, _, count = a.get("key", "").rpartition("x")
        try:
            out.setdefault(a["entrypoint"], {})[op] = int(count)
        except ValueError:
            out.setdefault(a["entrypoint"], {})[a["key"]] = -1
    return out


def _census_facts() -> dict:
    from dynamo_tpu.ops.pallas.registry import (
        KERNELS,
        audit_cases,
        fallback_census,
        probe_coverage,
    )

    geoms: dict[str, list] = {}
    for case in audit_cases():
        geoms.setdefault(case["kernel"], []).append(case["name"])
    probed = probe_coverage()
    return {
        "kernels": {
            name: {
                "module": meta["module"],
                "placeholder": bool(meta["placeholder"]),
                "probed": bool(probed.get(name, False)),
                "geometries": sorted(geoms.get(name, [])),
            }
            for name, meta in sorted(KERNELS.items())
        },
        "split": {
            "decode": "paged_decode_attention_mq",
            "ragged_prefill": "ragged_paged_prefill_attention",
            "unified": None,
        },
        "sh_fallback": fallback_census(),
        "shard_accepted": _shard_accepted_sh002(),
    }


def collect_kern_facts(budget: int = 1, seed_base: int = 0) -> dict:
    """The full kernel-plane fact snapshot: one entry per (kernel,
    geometry) of the registry matrix, plus the cross-plane census.
    budget > 1 or a nonzero seed base appends seeded fuzz geometries
    (canary-only; they never enter the manifest)."""
    from dynamo_tpu.ops.pallas.registry import audit_cases, fuzz_case

    cases = list(audit_cases())
    if budget > 1 or seed_base:
        cases += [fuzz_case(seed_base + i) for i in range(budget)]
    facts: dict[str, dict] = {}
    for case in cases:
        facts[f"pallas.{case['kernel']}[{case['name']}]"] = \
            _case_facts(case)
    facts["(kern-census)"] = _census_facts()
    return facts


# ---------------------------------------------------------------- check ----


def _is_fuzz(name: str) -> bool:
    return "[fuzz[" in name


def _check_census(census: dict) -> list[TraceFinding]:
    findings = []
    split = census.get("split", {})
    kernels = census.get("kernels", {})
    unified = split.get("unified")
    unified_real = bool(
        unified and not kernels.get(unified, {}).get("placeholder", True))
    if split.get("decode") and split.get("ragged_prefill") \
            and not unified_real:
        findings.append(TraceFinding(
            "(kern-census)", "KN006", "two-kernel-split",
            f"decode ({split['decode']}) and ragged prefill "
            f"({split['ragged_prefill']}) are separate kernels and the "
            "unified ragged kernel is a placeholder — ROADMAP item 2 "
            "(Ragged Paged Attention, arxiv 2604.15464) replaces both "
            "with ONE kernel; this acceptance is the machine-readable "
            "pin, and landing item 2 re-trips it",
        ))
    want = census.get("sh_fallback", {})
    have = census.get("shard_accepted", {})
    for ep in sorted(set(want) | set(have)):
        if want.get(ep) != have.get(ep):
            findings.append(TraceFinding(
                "(kern-census)", "KN006", f"sh-fallback:{ep}",
                f"registry fallback census {want.get(ep)} != shard "
                f"manifest accepted SH002 {have.get(ep)} for {ep} — "
                "the XLA-fallback gather acceptances and the kernel "
                "census must move together (retiring a kernel or "
                "landing the unified kernel updates BOTH planes)",
            ))
    for kname, meta in sorted(kernels.items()):
        if not meta.get("placeholder") and not meta.get("probed"):
            findings.append(TraceFinding(
                "(kern-census)", "KN006", f"probe:{kname}",
                f"registered kernel {kname} has no bench probe — "
                "probe coverage must equal registry coverage "
                "(benchmarks/probe_kernels.py builds from the "
                "registry's probe builders)",
            ))
    return findings


def check_kern_facts(facts: dict, manifest: Manifest,
                     drift: bool = True) -> list[TraceFinding]:
    """Findings = drift vs the committed manifest (KN005, resolved by
    fixing the kernel or re-snapshotting) + intrinsic defects
    (KN001-KN004, KN006, acceptable with a justification).  Fuzz
    entries are canary-only: never drift, never 'added'."""
    findings: list[TraceFinding] = []
    known = manifest.entrypoints
    if drift:
        for name in sorted(set(facts) - set(known)):
            if _is_fuzz(name):
                continue
            findings.append(TraceFinding(
                name, "KN005", "added",
                "fact entry not in the committed kern manifest — audit "
                "it and re-snapshot (`dynamo-tpu lint --kern "
                "--update-baseline`)",
            ))
        for name in sorted(set(known) - set(facts)):
            findings.append(TraceFinding(
                name, "KN005", "removed",
                "manifest entry no longer produced — re-snapshot if "
                "the kernel/geometry removal is intended",
            ))
    for name, f in sorted(facts.items()):
        if name == "(kern-census)":
            findings.extend(_check_census(f))
            continue
        vm = f["vmem"]
        if vm["resident_bytes"] > vm["budget_bytes"]:
            findings.append(TraceFinding(
                name, "KN001", "vmem-budget",
                f"per-grid-step resident {vm['resident_bytes']:,} B "
                f"(blocked {vm['blocked_bytes']:,} x double-buffer + "
                f"scratch {vm['scratch_bytes']:,}) exceeds the "
                f"per-core VMEM budget {vm['budget_bytes']:,} B — "
                "shrink the block/chunk geometry",
            ))
        for o in f["index_map"]["oob"]:
            findings.append(TraceFinding(
                name, "KN002",
                f"{o['operand']}@{','.join(map(str, o['step']))}",
                f"index map of {o['operand']} touches block "
                f"{o['block_index']} at grid step {o['step']} — "
                f"outside the valid block range {o['block_range']}",
            ))
        for r in f["index_map"]["races"]:
            findings.append(TraceFinding(
                name, "KN003", r["operand"],
                f"grid steps {r['steps']} all map {r['operand']} to "
                f"block {r['block_index']} NON-consecutively — a "
                "revisit-accumulate pattern is only sound on adjacent "
                "sequential steps; this is a write race under "
                "arbitrary grid order",
            ))
        if _canary_failed(f["canary"]):
            c = f["canary"]
            findings.append(TraceFinding(
                name, "KN004", "padding-leak",
                f"NaN canary reached live lanes ({c['nonfinite_live']}"
                f" nonfinite) or live lanes diverge from the oracle "
                f"(clean err {c['max_abs_err']}, poisoned err "
                f"{c['poisoned_max_abs_err']}, atol {c['atol']}, "
                f"zero-rows {'ok' if c['zero_rows_ok'] else 'VIOLATED'}"
                ") — padding/dead-slot data is influencing real "
                "outputs",
            ))
        committed = known.get(name)
        if not drift or committed is None or _is_fuzz(name):
            continue
        if f["pricing"] != committed.get("pricing"):
            findings.append(TraceFinding(
                name, "KN005", "pricing",
                f"kernel pricing drifted: {committed.get('pricing')} "
                f"-> {f['pricing']} — dtperf consumers see different "
                "costs; verify the kernel change, then re-snapshot",
            ))
        cvm = committed.get("vmem", {})
        if vm["resident_bytes"] != cvm.get("resident_bytes"):
            findings.append(TraceFinding(
                name, "KN005", "vmem",
                "per-grid-step VMEM drifted: "
                f"{cvm.get('resident_bytes')} -> "
                f"{vm['resident_bytes']} B — verify, then re-snapshot",
            ))
        if f["grid"] != committed.get("grid"):
            findings.append(TraceFinding(
                name, "KN005", "grid",
                f"grid drifted: {committed.get('grid')} -> {f['grid']}"
                " — verify the dispatch geometry, then re-snapshot",
            ))
    return sorted(findings)


# ------------------------------------------------------------------ CLI ----

# paths whose changes can affect kernel-plane facts (for `--changed`)
_TOUCHES = (
    "dynamo_tpu/ops/pallas",
    "dynamo_tpu/ops/kv_quant.py",
    "dynamo_tpu/ops/paged_attention.py",
    "dynamo_tpu/analysis/kerncheck.py",
    "dynamo_tpu/analysis/kern_manifest.json",
    "dynamo_tpu/analysis/shard_manifest.json",
)


def _kern_affected(root: Path) -> bool:
    from dynamo_tpu.analysis.cli import _git_changed_paths

    dirty = [str(p) for p in _git_changed_paths(root)]
    return any(frag in d for d in dirty for frag in _TOUCHES)


def _replay(token: str, fmt: str, out) -> int:
    """Re-run one fuzz geometry from its replay token (KN004 only —
    fuzz entries carry no committed baseline)."""
    import numpy as np

    from dynamo_tpu.ops.pallas.registry import fuzz_case

    seed = int(decode_token(token)["seed"])
    case = fuzz_case(seed)
    inp = case["build"]()
    clean = case["run"](inp, poisoned=False)
    canary = _canary_facts(case, inp, np.asarray(clean, np.float32))
    failed = _canary_failed(canary)
    if fmt == "json":
        doc = {"geometry": case["name"], "seed": seed,
               "canary": canary, "failed": failed}
        print(json.dumps(doc, indent=2, sort_keys=True), file=out)
    else:
        print(
            f"{case['name']}: clean err {canary['max_abs_err']} / "
            f"poisoned err {canary['poisoned_max_abs_err']} "
            f"(atol {canary['atol']}), {canary['nonfinite_live']} "
            f"nonfinite live lanes -> "
            f"{'PADDING LEAK' if failed else 'clean'}",
            file=out,
        )
    return 1 if failed else 0


def run_kern(args, out) -> int:
    """``dynamo-tpu lint --kern``: audit the registry geometry matrix,
    diff against the committed kern manifest, exit 1 on any
    non-accepted finding.  ``--update-baseline`` re-snapshots (pinned
    runs only); ``--replay dtk1.TOKEN`` re-runs one fuzz geometry."""
    token = getattr(args, "replay", None)
    if token:
        if not token.startswith(_TOKEN_PREFIX):
            print(f"not a dtkern replay token: {token[:16]!r} "
                  f"(expected {_TOKEN_PREFIX}...)", file=out)
            return 2
        return _replay(token, getattr(args, "fmt", "text"), out)

    manifest_path = Path(
        getattr(args, "manifest", None) or DEFAULT_MANIFEST_PATH)
    manifest = Manifest.load(manifest_path)
    budget, seed_base, pinned = _budget_env()
    root = Path(getattr(args, "root", None)
                or Path(__file__).resolve().parents[2])
    if getattr(args, "changed", False) and not _kern_affected(root):
        print("kernel plane unaffected by changed files", file=out)
        return 0
    facts = collect_kern_facts(budget=budget, seed_base=seed_base)
    # drift rules only judge the pinned default matrix: fuzz runs add
    # transient entries and must not demand a re-snapshot
    findings = check_kern_facts(facts, manifest, drift=pinned)

    if getattr(args, "update_baseline", False):
        if not pinned:
            print("refusing to update the kern manifest from a "
                  "non-default-budget/seed fuzz run", file=out)
            return 2
        intrinsic = [f for f in findings if f.rule != "KN005"]
        m = Manifest.from_facts(facts, intrinsic, manifest)
        m.header = _kern_header()
        m.save(manifest_path)
        print(
            f"kern manifest updated: {len(facts)} entries, "
            f"{len(intrinsic)} accepted finding"
            f"{'' if len(intrinsic) == 1 else 's'} -> {manifest_path}",
            file=out,
        )
        return 0

    fresh = manifest.filter(findings)
    n_accepted = len(findings) - len(fresh)
    n_fuzz = sum(1 for name in facts if _is_fuzz(name))
    if getattr(args, "fmt", "text") == "json":
        doc = {
            "findings": [f.to_json() for f in fresh],
            "accepted": n_accepted,
            "total": len(findings),
            "entries": sorted(facts),
            "fuzz": {
                "budget": budget, "seed_base": seed_base,
                "replay_tokens": _fuzz_tokens(fresh, facts),
            },
        }
        print(json.dumps(doc, indent=2, sort_keys=True), file=out)
    else:
        for f in fresh:
            print(f.render(), file=out)
        for name, tok in sorted(_fuzz_tokens(fresh, facts).items()):
            print(f"  replay: dynamo-tpu lint --kern --replay {tok}",
                  file=out)
        print(
            f"{len(fresh)} kern finding{'s' if len(fresh) != 1 else ''}"
            f" ({n_accepted} accepted) over {len(facts)} entries"
            + (f" incl. {n_fuzz} fuzz geometries" if n_fuzz else ""),
            file=out,
        )
    return 1 if fresh else 0


def _fuzz_tokens(fresh: list[TraceFinding], facts: dict) -> dict:
    """entrypoint -> replay token for every fresh finding on a fuzz
    geometry (the artifact the nightly job uploads)."""
    tokens = {}
    for f in fresh:
        if not _is_fuzz(f.entrypoint):
            continue
        geometry = facts[f.entrypoint]["geometry"]
        seed = int(geometry.split("ragged-")[1].rstrip("]"))
        tokens[f.entrypoint] = encode_token({"seed": seed})
    return tokens
