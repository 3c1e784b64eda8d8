"""Runtime reconciliation of the dtperf roofline model (predicted vs
measured dispatch latency).

The perf lint plane (``analysis/perfcheck.py``) prices entrypoint
jaxprs statically; this module closes the loop at runtime.  Each
engine dispatch site *offers* its jitted callable and live operand
shapes once per dispatch kind (``offer`` converts everything to
``ShapeDtypeStruct`` eagerly — no device arrays are retained — and is
a dict-lookup no-op afterwards).  The roofline prediction itself is
computed lazily on first read (``predicted_ms``), off the dispatch hot
path, by tracing the offered signature through
``perfcheck.estimate_callable``.

``reconcile()`` joins the predictions against the per-kind measured
seconds the step timeline accumulates.  **"Measured" is device-facing
time: from the jitted call to the return of its ``device_get``**
(the dispatch phase plus the overlap and readback phases that follow it,
``dispatch_kinds[kind].seconds`` of ``step_timeline.snapshot()``), so it
holds the program's run time plus launch and readback latency.  Until
PR 25 it was the dispatch phase alone — on an asynchronous backend the
*enqueue*, a few hundred microseconds whatever the program costs — and
the ratio below was wrong on the chip.  The rows go to ``/metrics`` as

    dynamo_tpu_perf_predicted_dispatch_ms{kind}
    dynamo_tpu_perf_measured_dispatch_ms{kind}
    dynamo_tpu_perf_model_error_ratio{kind}      (predicted/measured)

and that serve_bench prints as the predicted-vs-measured table.  A
ratio near 1 means the static gate's tolerance bands are meaningful;
a drifting ratio is itself the signal that the cost model needs
re-calibration (new kernel, new fusion behavior, hardware change).

Process-global singleton with a ``reset()`` test hook, same idiom as
``engine/counters.py``.  Never raises into the engine: a prediction
failure is recorded as None and reported as an absent gauge.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

__all__ = ["PerfModel", "perf_model"]


def _shape_only(tree):
    """Pytree of device arrays -> pytree of ShapeDtypeStructs (non-array
    leaves pass through; they trace as weak-typed scalars)."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x

    return jax.tree.map(leaf, tree)


class PerfModel:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        """Test isolation hook."""
        self.enabled = True
        # kind -> {fn, args, kw, statics, predicted (dict|None|"pending")}
        self._entries: dict[str, dict] = {}

    # ------------------------------------------------------------ hot path
    def wants(self, kind: str) -> bool:
        """True until a dispatch of this kind has been offered — the
        per-dispatch cost afterwards is this one dict lookup."""
        return self.enabled and kind not in self._entries

    def offer(self, kind: str, fn: Callable, args: tuple,
              kw: Optional[dict] = None,
              statics: Optional[dict] = None) -> None:
        """Record one dispatch signature: positional operands, device
        kwarg operands, and static kwargs.  Shapes are captured
        eagerly (no device-array references survive this call); the
        prediction is traced lazily on first read."""
        if not self.wants(kind):
            return
        try:
            entry = {
                "fn": fn,
                "args": _shape_only(tuple(args)),
                "kw": _shape_only(dict(kw or {})),
                "statics": dict(statics or {}),
                "predicted": "pending",
            }
        except Exception:
            return  # monitoring must never break the dispatch
        with self._lock:
            self._entries.setdefault(kind, entry)

    # ------------------------------------------------------------- readers
    def kinds(self) -> list[str]:
        return sorted(self._entries)

    def predicted(self, kind: str) -> Optional[dict]:
        """Full roofline estimate for an offered kind (traced on first
        call, cached; None if never offered or the trace failed)."""
        e = self._entries.get(kind)
        if e is None:
            return None
        if e["predicted"] != "pending":
            return e["predicted"]
        with self._lock:
            if e["predicted"] != "pending":
                return e["predicted"]
            try:
                import warnings

                # lazy import: obs stays a zero-dependency base layer;
                # the analysis plane is only pulled in when someone
                # actually reads a prediction
                from dynamo_tpu.analysis.perfcheck import (
                    estimate_callable,
                )

                fn, statics = e["fn"], e["statics"]
                names = sorted(e["kw"])
                pos = tuple(e["args"])
                npos = len(pos)
                kw_vals = tuple(e["kw"][n] for n in names)

                def call(*a):
                    kws = dict(zip(names, a[npos:]))
                    kws.update(statics)
                    return fn(*a[:npos], **kws)

                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    e["predicted"] = estimate_callable(
                        call, pos + kw_vals)
            except Exception:
                e["predicted"] = None
        return e["predicted"]

    def predicted_ms(self, kind: str) -> Optional[float]:
        est = self.predicted(kind)
        if est is None:
            return None
        return est["predicted"]["total_ms"]

    def reconcile(self) -> list[dict]:
        """Predicted-vs-measured rows per dispatch kind, joining the
        lazy roofline predictions with the step timeline's per-kind
        device-facing seconds (dispatch -> readback returned)."""
        from dynamo_tpu.obs.timeline import step_timeline

        snap = step_timeline.snapshot()
        measured = snap.get("dispatch_kinds", {})
        rows: list[dict] = []
        for kind in sorted(set(self.kinds()) | set(measured)):
            m = measured.get(kind, {})
            n = m.get("count", 0)
            meas_ms = (round(m.get("seconds", 0.0) / n * 1e3, 6)
                       if n else None)
            pred_ms = self.predicted_ms(kind)
            rows.append({
                "kind": kind,
                "predicted_ms": pred_ms,
                "measured_ms": meas_ms,
                "dispatches": n,
                # 4 significant digits, not 4 decimals: on CPU a v5e-
                # predicted ms is orders of magnitude under the measured
                # one and fixed rounding would collapse the ratio to 0
                "error_ratio": (
                    float(f"{pred_ms / meas_ms:.4g}")
                    if pred_ms is not None and meas_ms else None
                ),
            })
        return rows


perf_model = PerfModel()
