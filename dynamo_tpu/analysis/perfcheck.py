"""Perf-plane static analysis (dtperf): HLO-derived roofline cost model.

The compile plane (tracecheck) proves the hot loop *compiles* the way
the scheduler assumes — one executable per declared bucket, donation
aliased, no f32 upcasts.  It says nothing about how *fast* any of it
should be, and no perf claim in this repo has been measured on this
code (PERF.md).  This plane prices it analytically: for every registered jitted serving
entrypoint (the tracecheck registry — five EngineCore impls, draft
proposer, block scatter, Llama/DeepSeek forwards, Pallas ops via their
XLA fallback lowerings — plus the ring-attention shard_map body traced
over an abstract 4-chip mesh), the jaxpr is walked **shape-only on
CPU** and every equation is priced:

- ``dot_general`` / ``conv_general_dilated``: ``2 * out_size * K``
  FLOPs (dtype-aware — int8 dots run at 2x the bf16 MXU rate on v5e,
  f32 at half), bytes = operands + outputs.
- gather/scatter/dynamic-slice classes: bytes actually touched
  (gathered output + indices; updates read + written), no FLOPs.
- reductions/sorts: one FLOP per input element; bytes in + out.
- elementwise: one FLOP per output element (transcendentals weighted
  ``TRANSCENDENTAL_WEIGHT``); **bytes = output only** — the fusion
  assumption: XLA fuses producers into consumers, so an elementwise
  input is not re-read from HBM.  Layout-only ops (reshape /
  broadcast / squeeze) are free.
- control flow: ``scan`` multiplies by its trip count, ``cond`` takes
  the most expensive branch, ``while`` charges one body iteration
  (trip count is data-dependent; documented undercount).
- collectives (``psum`` / ``all_gather`` / ``reduce_scatter`` /
  ``all_to_all`` / ``ppermute``): a census entry (op x axis x payload
  bytes x axis size) plus an analytic ring cost from the
  ``obs.topology`` constants table (v5e ICI link bandwidth, DCN).
  ``shard_map`` regions bind their mesh axis sizes into the walk, so
  per-shard shapes and axis sizes are both exact.

Per (entrypoint, config) the facts are: total FLOPs, total HBM bytes,
arithmetic intensity, the collective census, and a predicted step
latency under the roofline

    max(sum_dtype FLOPs_dt / peak_dt, bytes / peak_bw)
        + sum collective_cost

Facts snapshot into the committed ``perf_manifest.json`` with the same
justification/``--update-baseline`` contract as the trace and wire
manifests.  The header pins ``obs.topology.CONSTANTS_VERSION`` so a
constants tweak re-trips PF001 explicitly rather than silently moving
every baseline.

Rules:

- PF001 predicted-latency-regression — predicted step latency grew
  beyond the tolerance band vs the manifest (also fires with key
  ``constants`` on a topology-constants version mismatch, and with
  ``added``/``removed`` for uncovered entrypoints).
- PF002 unexpected-collective — intrinsic, count-keyed like TR006:
  every census entry needs a justified acceptance; a new collective
  op, a new axis, or a count change trips the gate until re-justified.
- PF003 arithmetic-intensity-drop — a compute-bound entrypoint lost
  intensity (more bytes per FLOP: a fusion broke, a layout copy or
  upcast appeared on the hot path).
- PF004 bytes-regression — a bandwidth-bound entrypoint's HBM traffic
  grew beyond tolerance (decode-class dispatches live on this side of
  the roofline; bytes ARE their latency).

Caveats (also recorded in the manifest header): roofline figures
derive from the CPU lowering — fusion is assumed for elementwise
chains, and ``while`` trip counts are unknowable statically.
Pallas-backed ops are priced on BOTH sides of the dispatch decision:
the roofline row walks the XLA fallback jaxpr CPU produces, and a
``pallas_kernel`` row prices the registered kernel from
``ops/pallas/registry.py``'s analytic cost table (the same table the
kernel plane commits per-geometry into ``kern_manifest.json`` and the
kernels pin on-device via ``cost_estimate=``).  The model's job is
to *rank and gate*, not to be a simulator; its absolute calibration is
itself observable at runtime through the predicted-vs-measured
dispatch gauge (``obs/perfmodel.py``, ``/metrics``) and the
serve_bench reconciliation table.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Callable, Optional

from dynamo_tpu.analysis.tracecheck import (
    Entrypoint,
    Manifest,
    Signature,
    TraceFinding,
    _bytes_of,
    _closed_call,
    _sds,
    build_registry,
)
from dynamo_tpu.obs import topology

__all__ = [
    "DEFAULT_MANIFEST_PATH",
    "PERF_RULES",
    "build_perf_registry",
    "check_perf_facts",
    "collect_perf_facts",
    "estimate_callable",
    "estimate_jaxpr",
    "manifest_predictions",
    "run_perf",
]

DEFAULT_MANIFEST_PATH = Path(__file__).parent / "perf_manifest.json"

PERF_RULES = {
    "PF001": ("predicted-latency-regression",
              "roofline-predicted step latency regressed beyond the "
              "tolerance band vs the committed perf manifest"),
    "PF002": ("unexpected-collective",
              "collective census entry (op x axis x count) without a "
              "justified acceptance in the manifest"),
    "PF003": ("arithmetic-intensity-drop",
              "compute-bound entrypoint lost arithmetic intensity "
              "(bytes grew faster than FLOPs)"),
    "PF004": ("bytes-regression",
              "bandwidth-bound entrypoint's modeled HBM traffic grew "
              "beyond the tolerance band"),
}

# Tolerance bands: relative drift vs the committed manifest that is
# attributed to model noise (bucket arithmetic, jaxpr layout churn)
# rather than a real hot-path change.
LATENCY_REL_TOL = 0.05    # PF001
INTENSITY_REL_TOL = 0.10  # PF003
BYTES_REL_TOL = 0.05      # PF004

# One transcendental (exp/log/tanh/erf/...) costs this many
# VPU-element ops in the model — the lowered polynomial/lookup chains
# are several ops long (pl.CostEstimate counts them separately for the
# same reason).
TRANSCENDENTAL_WEIGHT = 8

_MANIFEST_NOTE = (
    "CPU-derived roofline facts (jax.make_jaxpr over ShapeDtypeStructs; "
    "elementwise chains assumed fused, while-loops charged one "
    "iteration): predictions rank and gate relative changes — absolute "
    "calibration is tracked at runtime by the predicted-vs-measured "
    "dispatch gauge on /metrics and must be validated by a chip run "
    "(PERF.md).  Pallas-backed "
    "ops carry BOTH sides of the dispatch decision: the roofline row "
    "prices the XLA fallback jaxpr CPU lowers, and `pallas_kernel` "
    "prices the registered kernel from ops/pallas/registry.py's "
    "analytic cost table — the same table kerncheck commits "
    "per-geometry into kern_manifest.json and the kernels pin "
    "on-device via cost_estimate=."
)

# Entrypoints whose TPU path dispatches a registered Pallas kernel:
# their signatures additionally get a `pallas_kernel` estimate from the
# kernel registry's cost table.
_PALLAS_PRICED = {
    "ops.paged_attention_layer": "paged_decode_attention_mq",
    "ops.ragged_prefill_attention": "ragged_paged_prefill_attention",
}


# ------------------------------------------------------------ cost walking ----


class Costs:
    """Accumulator for one jaxpr walk: FLOPs by dtype, HBM bytes, and
    the collective census."""

    def __init__(self) -> None:
        self.flops_by_dtype: dict[str, float] = {}
        self.bytes: float = 0.0
        # "op:axis" -> {count, payload_bytes, axis_size, cost_s}
        self.collectives: dict[str, dict] = {}

    @property
    def flops(self) -> float:
        return sum(self.flops_by_dtype.values())

    def add_flops(self, dtype: str, n: float) -> None:
        if n:
            self.flops_by_dtype[dtype] = \
                self.flops_by_dtype.get(dtype, 0.0) + n

    def add_collective(self, op: str, axes: tuple[str, ...],
                       axis_size: int, payload: float,
                       mult: float) -> None:
        key = f"{op}:{','.join(axes) if axes else '?'}"
        cost = topology.collective_cost_s(op, axis_size, payload)
        e = self.collectives.setdefault(key, {
            "count": 0, "payload_bytes": 0.0, "axis_size": axis_size,
            "cost_s": 0.0,
        })
        e["count"] += int(mult)
        e["payload_bytes"] += payload * mult
        e["cost_s"] += cost * mult

    def merge_max(self, other: "Costs") -> None:
        """Branch merge (cond): keep the more expensive side per term."""
        for dt, n in other.flops_by_dtype.items():
            self.flops_by_dtype[dt] = max(
                self.flops_by_dtype.get(dt, 0.0), n)
        self.bytes = max(self.bytes, other.bytes)
        for k, e in other.collectives.items():
            mine = self.collectives.get(k)
            if mine is None or e["cost_s"] > mine["cost_s"]:
                self.collectives[k] = dict(e)


# Layout-only primitives: no math, and XLA either elides them or folds
# them into a neighbor's loop nest.
_FREE_PRIMS = {
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "copy",
    "stop_gradient", "bitcast_convert_type", "sharding_constraint",
    "device_put", "sub_byte_view", "pvary", "psum_invariant",
}

# Data-movement primitives: bytes dominate, FLOPs ~ 0.  Value is a
# callable (eqn) -> bytes.
def _io_bytes(eqn) -> float:
    return (sum(_bytes_of(v.aval) for v in eqn.invars)
            + sum(_bytes_of(v.aval) for v in eqn.outvars))


def _out_bytes(eqn) -> float:
    return sum(_bytes_of(v.aval) for v in eqn.outvars)


_TRANSCENDENTALS = {
    "exp", "exp2", "expm1", "log", "log1p", "log2", "tanh", "logistic",
    "erf", "erf_inv", "erfc", "sin", "cos", "tan", "asin", "acos",
    "atan", "atan2", "sinh", "cosh", "pow", "rsqrt", "sqrt", "cbrt",
    "digamma", "lgamma",
}

_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "ppermute", "pbroadcast",
}

# psum-family primitives use param "axes"; the rest use "axis_name".
def _collective_axes(eqn) -> tuple[str, ...]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        return (ax,)
    return tuple(str(a) for a in ax)


def _dot_flops(eqn) -> tuple[str, float]:
    """2 * out_size * K from dimension_numbers; dtype from the lhs (or
    the requested accumulation type)."""
    lhs = eqn.invars[0].aval
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    k = 1
    for i in lhs_contract:
        k *= lhs.shape[i]
    out_size = sum(int(v.aval.size) for v in eqn.outvars)
    return str(lhs.dtype), 2.0 * out_size * k


def _conv_flops(eqn) -> tuple[str, float]:
    """2 * out_size * (kernel spatial x in-channel) per group."""
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    groups = int(eqn.params.get("feature_group_count", 1) or 1)
    dn = eqn.params.get("dimension_numbers")
    # rhs layout: spatial dims x in/group x out; per-output-element work
    # is rhs.size / out_channels
    out_feat = rhs.shape[dn.rhs_spec[0]] if dn is not None else \
        rhs.shape[-1]
    per_out = rhs.size / max(1, out_feat)
    return str(lhs.dtype), 2.0 * out.size * per_out / max(1, groups)


def _scatter_bytes(eqn) -> float:
    """Updates are read and written; indices read; the operand
    pass-through aliases (donation / XLA in-place) rather than
    rewriting the pool."""
    avals = [v.aval for v in eqn.invars[1:]]  # skip operand
    return 2.0 * sum(_bytes_of(a) for a in avals)


def _subjaxprs(eqn):
    """Sub-jaxprs of an eqn, handling both ClosedJaxpr params (pjit,
    scan, custom_*) and raw Jaxpr params (shard_map)."""
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    yield x.jaxpr
                elif hasattr(x, "eqns"):
                    yield x


def _walk(jaxpr, acc: Costs, mult: float,
          axis_env: dict[str, int]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name

        if name == "scan":
            length = float(eqn.params.get("length", 1) or 1)
            for sub in _subjaxprs(eqn):
                _walk(sub, acc, mult * length, axis_env)
            continue
        if name == "while":
            # trip count is data-dependent: charge one iteration of the
            # body (documented undercount; serving loops are scans)
            body = eqn.params.get("body_jaxpr")
            if body is not None:
                _walk(body.jaxpr, acc, mult, axis_env)
            continue
        if name == "cond":
            branches = [
                b.jaxpr for b in eqn.params.get("branches", ())
            ]
            worst = Costs()
            for b in branches:
                side = Costs()
                _walk(b, side, mult, axis_env)
                worst.merge_max(side)
            for dt, n in worst.flops_by_dtype.items():
                acc.add_flops(dt, n)
            acc.bytes += worst.bytes
            for k, e in worst.collectives.items():
                mine = acc.collectives.setdefault(k, {
                    "count": 0, "payload_bytes": 0.0,
                    "axis_size": e["axis_size"], "cost_s": 0.0,
                })
                mine["count"] += e["count"]
                mine["payload_bytes"] += e["payload_bytes"]
                mine["cost_s"] += e["cost_s"]
            continue
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            inner_env = dict(axis_env)
            if mesh is not None:
                inner_env.update(
                    {str(k): int(v) for k, v in dict(mesh.shape).items()}
                )
            for sub in _subjaxprs(eqn):
                _walk(sub, acc, mult, inner_env)
            continue

        if name in _COLLECTIVE_PRIMS:
            axes = _collective_axes(eqn)
            axis_size = 1
            for a in axes:
                axis_size *= axis_env.get(a, 1)
            payload = float(sum(_bytes_of(v.aval) for v in eqn.invars))
            acc.add_collective(name, axes, axis_size, payload, mult)
            continue

        if name in _FREE_PRIMS:
            continue
        # NOTE: the named classes below must come before the generic
        # sub-jaxpr recursion — scatter carries an update_jaxpr param
        # and would otherwise be priced as its (scalar) combiner
        if name == "dot_general":
            dt, f = _dot_flops(eqn)
            acc.add_flops(dt, f * mult)
            acc.bytes += _io_bytes(eqn) * mult
        elif name == "conv_general_dilated":
            dt, f = _conv_flops(eqn)
            acc.add_flops(dt, f * mult)
            acc.bytes += _io_bytes(eqn) * mult
        elif name in ("gather", "take", "take_along_axis"):
            # touched bytes: the gathered output + the index tensor
            idx = _bytes_of(eqn.invars[1].aval) if len(eqn.invars) > 1 \
                else 0
            acc.bytes += (_out_bytes(eqn) + idx) * mult
        elif name in ("dynamic_slice", "slice"):
            acc.bytes += _out_bytes(eqn) * mult
        elif name.startswith("scatter") or name == "dynamic_update_slice":
            acc.bytes += _scatter_bytes(eqn) * mult
            if "add" in name or "mul" in name:
                upd = eqn.invars[-1].aval
                acc.add_flops(str(upd.dtype), float(upd.size) * mult)
        elif name in ("concatenate", "pad", "transpose", "rev"):
            acc.bytes += _io_bytes(eqn) * mult
        elif name in ("sort", "top_k", "approx_top_k"):
            n = max(2, int(eqn.invars[0].aval.size))
            acc.add_flops(str(eqn.invars[0].aval.dtype),
                          n * math.log2(n) * mult)
            acc.bytes += _io_bytes(eqn) * mult
        elif name.startswith("reduce_") or name.startswith("cum") or \
                name in ("argmax", "argmin"):
            src = eqn.invars[0].aval
            acc.add_flops(str(src.dtype), float(src.size) * mult)
            acc.bytes += _io_bytes(eqn) * mult
        elif name == "convert_element_type":
            # a widening/narrowing pass re-materializes: both sides move
            acc.bytes += _io_bytes(eqn) * mult
        elif name == "iota":
            acc.bytes += _out_bytes(eqn) * mult
        else:
            # transparent wrappers: pjit, closed_call, custom_jvp/vjp,
            # remat — price the body
            subs = list(_subjaxprs(eqn))
            if subs:
                for sub in subs:
                    _walk(sub, acc, mult, axis_env)
                continue
            # elementwise default under the fusion assumption: one
            # (weighted) FLOP per output element, output bytes only
            out = eqn.outvars[0].aval
            if not hasattr(out, "size"):
                continue
            w = TRANSCENDENTAL_WEIGHT if name in _TRANSCENDENTALS else 1
            acc.add_flops(str(out.dtype), float(out.size) * w * mult)
            acc.bytes += _out_bytes(eqn) * mult


# ---------------------------------------------------------------- roofline ----


def _roofline(acc: Costs, topo_name: str = topology.DEFAULT_TOPOLOGY) \
        -> dict:
    topo = topology.TOPOLOGIES[topo_name]
    peaks = topo["peak_flops"]
    compute_s = sum(
        n / peaks.get(dt, topo["default_flops"])
        for dt, n in acc.flops_by_dtype.items()
    )
    memory_s = acc.bytes / topo["hbm_bw"]
    collective_s = sum(e["cost_s"] for e in acc.collectives.values())
    total_s = max(compute_s, memory_s) + collective_s
    return {
        "compute_ms": round(compute_s * 1e3, 6),
        "memory_ms": round(memory_s * 1e3, 6),
        "collective_ms": round(collective_s * 1e3, 6),
        "total_ms": round(total_s * 1e3, 6),
        "bound": "compute" if compute_s >= memory_s else "bandwidth",
    }


def estimate_jaxpr(jaxpr, axis_env: Optional[dict[str, int]] = None) \
        -> dict:
    """Price an (open) jaxpr: FLOPs/bytes/census + roofline dict."""
    acc = Costs()
    _walk(jaxpr, acc, 1.0, dict(axis_env or {}))
    flops = int(acc.flops)
    nbytes = int(acc.bytes)
    return {
        "flops": flops,
        "flops_by_dtype": {
            dt: int(n) for dt, n in sorted(acc.flops_by_dtype.items())
        },
        "bytes": nbytes,
        "intensity": round(flops / nbytes, 4) if nbytes else 0.0,
        "collectives": {
            k: {
                "count": e["count"],
                "payload_bytes": int(e["payload_bytes"]),
                "axis_size": e["axis_size"],
                "cost_us": round(e["cost_s"] * 1e6, 3),
            }
            for k, e in sorted(acc.collectives.items())
        },
        "predicted": _roofline(acc),
    }


def estimate_callable(fn: Callable, args: tuple,
                      statics: Optional[dict] = None,
                      axis_env: Optional[dict[str, int]] = None) -> dict:
    """Trace ``fn(*args, **statics)`` shape-only (args are pytrees of
    ShapeDtypeStruct) and price the jaxpr.  This is the entry the
    runtime reconciliation layer (``obs/perfmodel.py``) uses to predict
    a live dispatch's latency from its offered signature."""
    import jax

    statics = dict(statics or {})
    closed = jax.make_jaxpr(lambda *a: fn(*a, **statics))(*args)
    return estimate_jaxpr(closed.jaxpr, axis_env)


# ---------------------------------------------------------------- registry ----


def _ring_attention_entrypoint(axis_size: int = 4) -> Entrypoint:
    """The one real collective site: the ring-attention shard_map body,
    traced over an ABSTRACT sp-axis mesh (no devices needed), so the
    committed census carries live ppermute entries with a nonzero ICI
    cost term."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.ring_attention import ring_attention_inner
    from dynamo_tpu.utils.mesh import AXIS_SP, abstract_mesh

    inner = functools.partial(ring_attention_inner, axis_name=AXIS_SP)
    seq, pos = P(None, AXIS_SP, None, None), P(None, AXIS_SP)
    wrapped = jax.shard_map(
        inner, mesh=abstract_mesh(axis_size, (AXIS_SP,)),
        in_specs=(seq, seq, seq, pos, pos), out_specs=seq, check_vma=False)
    h, hk, d = 4, 2, 8
    bf16, i32 = jnp.bfloat16, jnp.int32

    def build(s):
        args = (_sds((1, s, h, d), bf16), _sds((1, s, hk, d), bf16),
                _sds((1, s, hk, d), bf16), _sds((1, s), i32),
                _sds((1, s), i32))
        return Signature(f"s={s}", args, {})

    return Entrypoint(
        name=f"ops.ring_attention[sp{axis_size}]",
        axes={"s": [64, 128]},
        build=build,
        raw_fn=wrapped,
        representatives=[dict(s=128)],
    )


def _mlp_reference_entrypoint() -> Entrypoint:
    """The gated-MLP projection chain at llama3b-v5e dims — the
    MXU-bound share of a real prefill step, priced on its own.

    Under the XLA-fallback lowerings the *whole-entrypoint* intensities
    all land on the bandwidth side of the roofline (the fallback
    attention materializes f32 score matrices and gathers the padded KV
    pool — the Pallas kernels stream both on-chip).  This entry keeps a
    genuinely compute-bound row live in the committed manifest so the
    bound classifier and PF003 are exercised on real dims, not only on
    synthetic test fixtures."""
    import jax.numpy as jnp

    hidden, inter, tokens = 3072, 8192, 8192
    bf16 = jnp.bfloat16

    def mlp(x, w_gate, w_up, w_down):
        import jax

        return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down

    def build(t):
        args = (_sds((t, hidden), bf16), _sds((hidden, inter), bf16),
                _sds((hidden, inter), bf16), _sds((inter, hidden), bf16))
        return Signature(f"t={t}", args, {})

    return Entrypoint(
        name="roofline.mlp_reference[llama3b-v5e]",
        axes={"t": [tokens]},
        build=build,
        raw_fn=mlp,
        representatives=[dict(t=tokens)],
    )


def build_perf_registry() -> list[Entrypoint]:
    """The tracecheck registry plus perf-only entries: the sequence-
    parallel ring attention body (the one real collective site — only
    this plane prices collectives) and the compute-bound MLP reference
    chain.  Model forwards additionally get their prefill phase as a
    priced representative (tracecheck only eval-shapes it)."""
    eps = build_registry()
    for ep in eps:
        if "phase" in ep.axes:
            reps = list(ep.representatives)
            if {"phase": "prefill"} not in reps:
                reps.append({"phase": "prefill"})
            ep.representatives = reps
    eps.append(_ring_attention_entrypoint())
    eps.append(_mlp_reference_entrypoint())
    return eps


def _pallas_kernel_estimate(ep_name: str, sig: Signature) \
        -> Optional[dict]:
    """Price the kernel the TPU path dispatches for this signature from
    the kernel registry's analytic cost table — dims read off the
    signature's ShapeDtypeStructs, context at the worst-case static
    bound (every row at full M*Bs), the same bound the kernels pin
    on-device via ``cost_estimate=``.  Returns None for entrypoints
    with no registered kernel."""
    base = ep_name.partition("[")[0]
    kernel = _PALLAS_PRICED.get(base)
    if kernel is None:
        return None
    from dynamo_tpu.ops.pallas import registry as kreg

    if base == "ops.paged_attention_layer":
        q, cache, _, bt = sig.args[:4]
        b, s_q, h, d = q.shape
        # cache leaf layout: [L, N, 2, Bs, Hk*D] (models/llama.py)
        bs, hkd = cache.shape[3], cache.shape[4]
        cost = kreg.decode_kernel_cost(
            b, s_q, h, hkd // d, d, bs, bt.shape[1],
            [bt.shape[1] * bs] * b, cache_bytes=cache.dtype.itemsize,
            q_bytes=q.dtype.itemsize)
    else:  # ops.ragged_prefill_attention
        q, _, _, cache, _, bt = sig.args[:6]
        _, t, h, d = q.shape
        bs, hkd = cache.shape[3], cache.shape[4]
        cost = kreg.ragged_kernel_cost(
            t, h, hkd // d, d, bs, bt.shape[1],
            [bt.shape[1] * bs] * bt.shape[0],
            cache_bytes=cache.dtype.itemsize)
    return {"kernel": kernel, **cost}


def collect_perf_facts(
        registry: Optional[list[Entrypoint]] = None) -> dict:
    """Roofline facts for every registered entrypoint, per
    representative signature (the same config matrix tracecheck
    eval-shapes).  Pure shape-level work: make_jaxpr over
    ShapeDtypeStructs — no weights, no compiles, no model math.
    Pallas-backed ops get the registry's kernel pricing attached
    alongside the fallback roofline (``pallas_kernel``)."""
    registry = registry if registry is not None else build_perf_registry()
    facts: dict[str, dict] = {}
    for ep in registry:
        fn = ep.raw_fn if ep.raw_fn is not None else ep.jit_fn
        if fn is None:
            continue
        sigs: dict[str, dict] = {}
        for rep in ep.representatives:
            sig = ep.build(**rep)
            if sig is None:
                continue
            est = estimate_callable(fn, sig.args, sig.statics)
            kern = _pallas_kernel_estimate(ep.name, sig)
            if kern is not None:
                est["pallas_kernel"] = kern
            sigs[sig.label] = est
        facts[ep.name] = {"signatures": sigs}
    return facts


# ------------------------------------------------------------------- check ----


def check_perf_facts(facts: dict, manifest: Manifest) \
        -> list[TraceFinding]:
    """Findings = drift (facts vs the committed roofline snapshot,
    PF001/PF003/PF004 with tolerance bands) + the intrinsic collective
    census (PF002, count-keyed acceptances like TR006).  Drift is
    resolved by fixing the regression or re-snapshotting with
    ``--update-baseline``; PF002 entries need a justification."""
    findings: list[TraceFinding] = []
    known = manifest.entrypoints

    header = manifest.header or {}
    committed_ver = header.get("constants_version")
    if known and committed_ver != topology.CONSTANTS_VERSION:
        findings.append(TraceFinding(
            "(topology)", "PF001", "constants",
            f"topology constants version drifted: manifest pins "
            f"{committed_ver!r}, obs.topology has "
            f"{topology.CONSTANTS_VERSION!r} — every predicted latency "
            "moved; review the constants change and re-snapshot "
            "(`dynamo-tpu lint --perf --update-baseline`)",
        ))

    for name in sorted(set(facts) - set(known)):
        findings.append(TraceFinding(
            name, "PF001", "added",
            "entrypoint has no committed roofline baseline — audit the "
            "prediction and re-snapshot "
            "(`dynamo-tpu lint --perf --update-baseline`)",
        ))
    for name in sorted(set(known) - set(facts)):
        findings.append(TraceFinding(
            name, "PF001", "removed",
            "manifest entrypoint no longer registered — re-snapshot if "
            "the removal is intended",
        ))

    for name, f in sorted(facts.items()):
        committed = known.get(name) or {}
        old_sigs = committed.get("signatures", {})
        for label, est in sorted(f.get("signatures", {}).items()):
            old = old_sigs.get(label)

            # PF002 is intrinsic: every census entry fires with its
            # count embedded in the acceptance key, so a new collective
            # op/axis OR a count change invalidates the accepted entry
            for ckey, c in est.get("collectives", {}).items():
                findings.append(TraceFinding(
                    name, "PF002", f"{label}:{ckey}x{c['count']}",
                    f"{c['count']} {ckey} collective(s) over "
                    f"{c['axis_size']} chips moving "
                    f"{c['payload_bytes']:,} B "
                    f"(+{c['cost_us']:.1f} us predicted) — accept with "
                    "a justification only if the collective is by "
                    "design on this dispatch",
                ))

            if old is None:
                if known:  # entrypoint-level "added" already fired
                    if name in known:
                        findings.append(TraceFinding(
                            name, "PF001", f"{label}:added",
                            "signature has no committed roofline "
                            "baseline — re-snapshot",
                        ))
                continue

            new_ms = est["predicted"]["total_ms"]
            old_ms = old["predicted"]["total_ms"]
            if old_ms > 0 and new_ms > old_ms * (1 + LATENCY_REL_TOL):
                findings.append(TraceFinding(
                    name, "PF001", label,
                    f"predicted step latency regressed "
                    f"{old_ms:.4f} -> {new_ms:.4f} ms "
                    f"(+{(new_ms / old_ms - 1) * 100:.1f}%, tolerance "
                    f"{LATENCY_REL_TOL * 100:.0f}%): compute "
                    f"{est['predicted']['compute_ms']:.4f} ms, memory "
                    f"{est['predicted']['memory_ms']:.4f} ms, "
                    f"collectives "
                    f"{est['predicted']['collective_ms']:.4f} ms — fix "
                    "the hot path or justify via --update-baseline",
                ))

            old_int, new_int = old["intensity"], est["intensity"]
            if old["predicted"]["bound"] == "compute" and old_int > 0 \
                    and new_int < old_int * (1 - INTENSITY_REL_TOL):
                findings.append(TraceFinding(
                    name, "PF003", label,
                    f"arithmetic intensity dropped {old_int:.2f} -> "
                    f"{new_int:.2f} FLOP/B on a compute-bound "
                    "entrypoint: bytes grew faster than FLOPs (broken "
                    "fusion, layout copy, or upcast on the hot path)",
                ))

            if old["predicted"]["bound"] == "bandwidth" and \
                    old["bytes"] > 0 and \
                    est["bytes"] > old["bytes"] * (1 + BYTES_REL_TOL):
                findings.append(TraceFinding(
                    name, "PF004", label,
                    f"modeled HBM traffic grew {old['bytes']:,} -> "
                    f"{est['bytes']:,} B "
                    f"(+{(est['bytes'] / old['bytes'] - 1) * 100:.1f}%) "
                    "on a bandwidth-bound entrypoint — bytes ARE its "
                    "latency on this side of the roofline",
                ))
    return sorted(findings)


def _perf_header() -> dict:
    return {
        "note": _MANIFEST_NOTE,
        "topology": topology.DEFAULT_TOPOLOGY,
        "constants_version": topology.CONSTANTS_VERSION,
        "tolerances": {
            "latency_rel": LATENCY_REL_TOL,
            "intensity_rel": INTENSITY_REL_TOL,
            "bytes_rel": BYTES_REL_TOL,
        },
    }


# ------------------------------------------------------------- predictions ----


_PREDICTION_CACHE: Optional[list[dict]] = None


def manifest_predictions(path: Optional[Path] = None) -> list[dict]:
    """Flat predicted-latency rows from the *committed* manifest —
    what ``/metrics`` exports as
    ``dynamo_tpu_perf_predicted_step_ms{entrypoint,config,signature}``.
    Reads the JSON once per process (no jax, no tracing)."""
    global _PREDICTION_CACHE
    if path is None and _PREDICTION_CACHE is not None:
        return _PREDICTION_CACHE
    p = Path(path) if path is not None else DEFAULT_MANIFEST_PATH
    rows: list[dict] = []
    if p.is_file():
        try:
            doc = json.loads(p.read_text())
        except (OSError, ValueError):
            doc = {}
        for name, f in sorted(doc.get("entrypoints", {}).items()):
            base, _, cfg = name.partition("[")
            cfg = cfg.rstrip("]")
            for label, est in sorted(
                    f.get("signatures", {}).items()):
                rows.append({
                    "entrypoint": base,
                    "config": cfg,
                    "signature": label,
                    "predicted_ms": est["predicted"]["total_ms"],
                    "bound": est["predicted"]["bound"],
                })
    if path is None:
        _PREDICTION_CACHE = rows
    return rows


# --------------------------------------------------------------------- CLI ----


def run_perf(args, out) -> int:
    """`dynamo-tpu lint --perf`: text or stable JSON, exit 1 on any
    non-accepted finding, `--update-baseline` re-snapshots the manifest
    (carrying justifications by key) and pins the topology-constants
    version in the header."""
    manifest_path = Path(
        getattr(args, "manifest", None) or DEFAULT_MANIFEST_PATH
    )
    manifest = Manifest.load(manifest_path)
    facts = collect_perf_facts()
    findings = check_perf_facts(facts, manifest)

    if getattr(args, "update_baseline", False):
        # drift findings (PF001/PF003/PF004) are resolved by the
        # snapshot itself; the intrinsic census (PF002) becomes
        # accepted entries
        intrinsic = [f for f in findings if f.rule == "PF002"]
        new = Manifest.from_facts(facts, intrinsic, manifest)
        new.header = _perf_header()
        new.save(manifest_path)
        print(
            f"perf manifest updated: {len(facts)} entrypoints, "
            f"{len(intrinsic)} accepted finding"
            f"{'' if len(intrinsic) == 1 else 's'} -> {manifest_path}",
            file=out,
        )
        return 0

    fresh = manifest.filter(findings)
    n_accepted = len(findings) - len(fresh)
    if getattr(args, "fmt", "text") == "json":
        doc = {
            "findings": [f.to_json() for f in fresh],
            "accepted": n_accepted,
            "total": len(findings),
            "entrypoints": sorted(facts),
        }
        print(json.dumps(doc, indent=2, sort_keys=True), file=out)
    else:
        for f in fresh:
            print(f.render(), file=out)
        print(
            f"{len(fresh)} perf finding{'s' if len(fresh) != 1 else ''} "
            f"({n_accepted} accepted) over {len(facts)} entrypoints",
            file=out,
        )
    return 1 if fresh else 0
