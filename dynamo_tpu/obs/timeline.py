"""Engine step timeline: per-phase wall-time attribution for
``EngineCore.step``, on the host's counters and in the profiler's trace.

The model is enter-based: :meth:`StepTimeline.begin` opens a step and its
first phase, ``enter(phase)`` closes the open phase and opens the next,
and :meth:`end` closes the last — every instant of a step lies in exactly
one phase, so the phase sum equals the step wall time **by construction**
(the only loss is float rounding).

**What a step is.**  The engine keeps at most one dispatch un-read-back
(``EngineCore._settle``): a step builds, uploads and issues dispatch N+1
and *then* reads back and finishes dispatch N.  So the ``readback`` and
``host_post`` of a step are, as a rule, those of the dispatch the step
before issued, and they run while the device executes the one this step
issued.  A step that cannot issue ahead reads back first and then issues
(the old order, all of one dispatch); one with nothing to issue only
finishes.  That is the one overlap of host and device the engine has.

Phases (in step order; the profiler span of each is ``dyn.<phase>``):

    kv_spill_restore  host<->device KV block traffic (_drain_offload)
    host_ops          cross-thread op/abort queues
    admission         _admit: block allocation, grammar budget, slots
    host_build        numpy dispatch-operand builds (tokens, block
                      tables, penalty buffers, grammar rows), rng split
    upload            the ONE batched jax.device_put per dispatch
    dispatch          the jitted call itself (trace/en-queue; on CPU
                      backends this includes compute)
    readback          jax.device_get — blocks until device compute
                      lands, so device time not overlapped with host
                      work shows up here (of the dispatch issued the step
                      before, when one was in flight)
    host_post         sampled-token append, stop conditions, emit

**One call, two sinks.**  The same clock reads feed the ``perf_counter``
aggregates and, while a ``jax.profiler`` session is open, one
``TraceAnnotation("dyn.<phase>", step=, kind=, t_mono_ns=)`` per phase on
the engine thread (one open at a time, never one around the whole step:
phases stay leaves at the depth of their call site).  ``step`` is the
busy-step index, ``kind`` the dispatch kind on dispatch/readback,
and ``t_mono_ns`` is ``time.monotonic_ns()`` at the open: trace time −
``t_mono_ns`` is the offset that puts anything stamped with
``time.monotonic`` on the device trace's axis.  The ``upload``,
``dispatch`` and ``readback`` events also say what the dispatch carried
(``enter(..., carried=)``): ``rows``, ``tokens`` (prompt tokens of a
prefill dispatch, rows of a decode) and ``ctx`` (the sum of its rows'
context lengths) — the engine builds them only while :meth:`profiling`.
With no session open a phase costs a flag test
(``TraceAnnotation.is_enabled()``) and no object.

The headline derived number is **host_gap_ms_per_turn** — wall time
per dispatching step spent *outside* dispatch+readback: the host's own
work per step.  While a dispatch is in flight that time runs under the
device program (hidden), and it is device time lost only in the steps
that read back first.  The aggregates are always on: per busy step about
twenty clock reads, two small dicts and a handful of float adds;
per-step *spans* of the dtspan plane are emitted only when that plane is
enabled.

``enter("dispatch", kind=...)`` names the **dispatch kind** (``step``,
``decode_multi``, ``prefill_ragged``, ``unified``, ``sp_prefill``,
``spec_verify``).  ``dispatch_kinds[kind].seconds`` is **device-facing**
time: the dispatch phase plus the readback phase that follows it, i.e.
enqueue → readback returned — not the enqueue alone, which on an
asynchronous backend is a few hundred microseconds whatever the program
costs.  ``enter("readback", kind=..., issued=False)`` books the readback
of a dispatch an earlier step issued to that dispatch's kind and counts
no dispatch.  It is the denominator of the dtperf predicted-vs-measured
gauge (``obs/perfmodel.py``).  At ``end`` a busy step's wall, and its
device-facing part (wall − host gap), are added to a **class** —
``prefill`` (``step``, ``prefill_ragged``, ``sp_prefill``), ``decode``
(``decode_multi``, ``spec_verify``) or ``mixed`` (``unified``, several
classes in one step, or no kind at all) — the class of the dispatch the
step **issued**, or, when it issued none, of what it finished; so the
class walls add up to ``wall_seconds_total`` and a class's steps count
its dispatches.  The class also gets the step's ``upload`` + ``dispatch``
(**launch**: what it costs the host to hand the device its next program)
and its ``readback`` (the host standing blocked on the device: **the
host's slack in that turn** — near zero, the host sets the pace and the
device waits); wall − launch − readback is the host's own work in a turn
of that class.  The readback of a turn is as a rule of the dispatch the
turn *before* issued, so a prefill turn's readback waits for a decode.
When the dtspan plane is enabled, ``end`` also emits one ``engine.step``
span per busy step carrying the phase breakdown and the roofline-predicted
dispatch envelope, which the Chrome export renders as a
predicted-vs-measured counter track.  Those spans share one trace of the
engine's own (``tracing.ENGINE_TRACE``, served at ``/debug/traces/engine``):
a request's trace holds the request's stages
(``engine.queue`` … ``engine.decode``, engine/core.py), whose
``first_step`` / ``last_step`` name the steps that served it.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional

__all__ = ["StepTimeline", "step_timeline", "PHASES", "CLASSES",
           "KIND_CLASS"]

PHASES = (
    "kv_spill_restore",
    "host_ops",
    "admission",
    "host_build",
    "upload",
    "dispatch",
    "readback",
    "host_post",
)

_DISPATCH_PHASES = ("upload", "dispatch", "readback")
# enqueue -> readback returned: what a dispatch kind's seconds cover, and
# what the host gap leaves out
_DEVICE_FACING = ("dispatch", "readback")
_SPAN_NAMES = {p: f"dyn.{p}" for p in PHASES}

CLASSES = ("prefill", "decode", "mixed")
KIND_CLASS = {
    "step": "prefill",
    "prefill_ragged": "prefill",
    "sp_prefill": "prefill",
    "decode_multi": "decode",
    "spec_verify": "decode",
    "unified": "mixed",
}


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at a timeline's first
    ``begin``: the HTTP front end reads this module without an engine."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class StepTimeline:
    """Process-global (one engine thread writes, metrics readers read;
    torn reads of monotonically-increasing floats are acceptable for
    monitoring)."""

    def __init__(self,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self._lock = threading.Lock()
        # injectable so simulated engines (load plane) can stamp steps
        # at virtual time; the default stays the high-resolution counter
        self._clock = clock
        self._annotation = None
        self.reset()

    def reset(self) -> None:
        """Test isolation hook."""
        self.steps_total = 0          # begin/end pairs seen
        self.busy_steps_total = 0     # steps that ran >= 1 device dispatch
        self.wall_s_total = 0.0       # busy-step wall time
        self.phase_s_total = {p: 0.0 for p in PHASES}
        self.host_gap_s_total = 0.0   # busy wall - dispatch - readback
        self.ewma_wall_s = 0.0
        self.ewma_host_gap_s = 0.0
        # device-facing seconds (dispatch -> readback returned) split by
        # jitted-entrypoint kind — the denominator of the dtperf gauge
        self.dispatch_kind_s: dict[str, float] = {}
        self.dispatch_kind_n: dict[str, int] = {}
        # busy steps by what they dispatched: count, wall, device-facing
        self.class_steps = {c: 0 for c in CLASSES}
        self.class_wall_s = {c: 0.0 for c in CLASSES}
        self.class_device_s = {c: 0.0 for c in CLASSES}
        # upload + dispatch, and readback, of those steps
        self.class_launch_s = {c: 0.0 for c in CLASSES}
        self.class_readback_s = {c: 0.0 for c in CLASSES}
        self._alpha = 0.05
        self._t0: Optional[float] = None
        self._t0_ns = 0
        self._last = 0.0
        self._phase = PHASES[0]
        self._kind: Optional[str] = None
        self._span = None
        self._carried: dict = {}
        self._phases: dict = {}
        self._step_kinds: dict = {}
        self._issued: set = set()

    # ------------------------------------------------------------ hot path
    def begin(self, phase: str = PHASES[0]) -> None:
        """Open a step and its first phase."""
        if self._annotation is None:
            self._annotation = _trace_annotation()
        now = self._clock()
        self._t0 = now
        self._last = now
        self._phases = {}
        self._step_kinds = {}
        self._issued = set()
        self._kind = None
        self._carried = {}
        self._t0_ns = time.monotonic_ns()
        self._open(phase)

    def profiling(self) -> bool:
        """Is a ``jax.profiler`` session open?  What a dispatch carried
        (``enter(carried=)``) is worth building only then."""
        return self._annotation is not None and self._annotation.is_enabled()

    def enter(self, phase: str, kind: Optional[str] = None,
              issued: bool = True, carried: Optional[dict] = None) -> None:
        """Close the open phase and open ``phase``.  ``kind`` (on
        ``dispatch``) names the jitted entrypoint; the readback that
        follows is booked to it too.  ``issued=False`` (on
        the ``readback`` of a dispatch that may be an earlier step's)
        books to ``kind`` and counts no dispatch.  ``carried`` (``rows``,
        ``tokens``, ``ctx``) is what the dispatch of this and the
        following upload / dispatch / readback events carried, until the
        next ``carried``; an empty dict says "not known"."""
        if self._t0 is None:
            return  # dispatch helper invoked outside step() (tests)
        self._close(self._clock())
        if carried is not None:
            self._carried = carried
        if kind is not None:
            self._kind = kind
            if issued:
                self._issued.add(kind)
                self.dispatch_kind_n[kind] = \
                    self.dispatch_kind_n.get(kind, 0) + 1
        self._open(phase)

    def _open(self, phase: str) -> None:
        self._phase = phase
        if self._annotation.is_enabled():
            kind = self._kind if phase in _DEVICE_FACING else None
            carried = self._carried if phase in _DISPATCH_PHASES else {}
            span = self._annotation(
                _SPAN_NAMES[phase], step=self.busy_steps_total,
                kind=kind or "", t_mono_ns=time.monotonic_ns(), **carried)
            span.__enter__()
            self._span = span

    def _close(self, now: float) -> None:
        delta = now - self._last
        self._last = now
        phase = self._phase
        self._phases[phase] = self._phases.get(phase, 0.0) + delta
        kind = self._kind
        if kind is not None and phase in _DEVICE_FACING:
            self.dispatch_kind_s[kind] = \
                self.dispatch_kind_s.get(kind, 0.0) + delta
            self._step_kinds[kind] = self._step_kinds.get(kind, 0.0) + delta
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def end(self) -> None:
        if self._t0 is None:
            return
        now = self._clock()
        self._close(now)
        phases = self._phases
        wall = now - self._t0
        t0_ns = self._t0_ns
        self._t0 = None
        busy = any(phases.get(p) for p in _DISPATCH_PHASES)
        self.steps_total += 1
        if not busy:
            return  # idle polls would drown the per-turn numbers
        facing = sum(phases.get(p, 0.0) for p in _DEVICE_FACING)
        gap = wall - facing
        self.busy_steps_total += 1
        self.wall_s_total += wall
        self.host_gap_s_total += gap
        for p, v in phases.items():
            self.phase_s_total[p] = self.phase_s_total.get(p, 0.0) + v
        classes = {KIND_CLASS.get(k, "mixed")
                   for k in (self._issued or self._step_kinds)}
        cls = classes.pop() if len(classes) == 1 else "mixed"
        self.class_steps[cls] += 1
        self.class_wall_s[cls] += wall
        self.class_device_s[cls] += facing
        self.class_launch_s[cls] += (phases.get("upload", 0.0)
                                     + phases.get("dispatch", 0.0))
        self.class_readback_s[cls] += phases.get("readback", 0.0)
        a = self._alpha
        self.ewma_wall_s = wall if self.busy_steps_total == 1 else (
            (1 - a) * self.ewma_wall_s + a * wall)
        self.ewma_host_gap_s = gap if self.busy_steps_total == 1 else (
            (1 - a) * self.ewma_host_gap_s + a * gap)
        self._emit_step_span(t0_ns, wall, phases)

    # ----------------------------------------------------------- trace emit
    def _emit_step_span(self, t0_ns: int, wall: float,
                        phases: dict) -> None:
        """One ``engine.step`` span per busy step when the tracing
        plane is on: the step's index, phase breakdown, per-kind dispatch
        ms, and the roofline-predicted dispatch envelope (the Chrome
        export turns the predicted/measured pair into a counter track).
        All under ``tracing.ENGINE_TRACE``, which ``/debug/traces/engine``
        fetches: a step serves every request in a slot, so it belongs
        to no request's trace."""
        from dynamo_tpu.obs import tracing

        if not tracing.enabled():
            return
        kinds = dict(self._step_kinds)
        attrs: dict = {
            "step": self.busy_steps_total - 1,
            "phases_ms": {
                p: round(v * 1e3, 3) for p, v in sorted(phases.items())
            },
            "dispatch_kinds": sorted(kinds),
            "measured_dispatch_ms": round(
                sum(kinds.values()) * 1e3, 3),
        }
        # predicted envelope: lazy roofline per offered kind — only
        # priced under tracing (first read traces the jaxpr once)
        try:
            from dynamo_tpu.obs.perfmodel import perf_model

            preds = [perf_model.predicted_ms(k) for k in kinds]
            if preds and all(p is not None for p in preds):
                attrs["predicted_dispatch_ms"] = round(sum(preds), 3)
        except Exception:
            pass  # monitoring must never break the step loop
        tracing.collector.add({
            "name": "engine.step",
            "trace": tracing.ENGINE_TRACE,
            "span": tracing._new_span_id(),
            "parent": None,
            "ts": t0_ns,
            "dur": int(wall * 1e9),
            "proc": tracing.process_name(),
            "attrs": attrs,
        })

    # ------------------------------------------------------------- readers
    @property
    def host_gap_ms_per_turn(self) -> float:
        """Mean host bubble per dispatching step — the committed
        before-number for ROADMAP item 3."""
        if not self.busy_steps_total:
            return 0.0
        return self.host_gap_s_total / self.busy_steps_total * 1e3

    def snapshot(self) -> dict:
        """Dict for /metrics rendering and serve_bench banking."""
        return {
            "steps_total": self.steps_total,
            "busy_steps_total": self.busy_steps_total,
            "wall_seconds_total": self.wall_s_total,
            "host_gap_ms_per_turn": self.host_gap_ms_per_turn,
            "ewma_wall_ms": self.ewma_wall_s * 1e3,
            "ewma_host_gap_ms": self.ewma_host_gap_s * 1e3,
            "phases": {p: self.phase_s_total.get(p, 0.0) for p in PHASES},
            # flat, so that a reader of top-level numbers gets them
            **{f"{c}_steps_total": self.class_steps[c] for c in CLASSES},
            **{f"{c}_wall_seconds_total": self.class_wall_s[c]
               for c in CLASSES},
            **{f"{c}_device_seconds_total": self.class_device_s[c]
               for c in CLASSES},
            **{f"{c}_launch_seconds_total": self.class_launch_s[c]
               for c in CLASSES},
            **{f"{c}_readback_seconds_total": self.class_readback_s[c]
               for c in CLASSES},
            "dispatch_kinds": {
                k: {
                    "seconds": self.dispatch_kind_s[k],
                    "count": self.dispatch_kind_n.get(k, 0),
                }
                for k in sorted(self.dispatch_kind_s)
            },
        }


step_timeline = StepTimeline()
