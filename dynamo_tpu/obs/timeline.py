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

**Did the device wait?**  The timeline answers it itself, untraced.  When
``EngineCore._settle`` makes a dispatch the one in flight it hands over a
non-blocking probe of it (:meth:`StepTimeline.in_flight`:
``jax.Array.is_ready`` of one output; under a mesh that covers the shards).
The probe of the newest program issued is polled wherever the clock is read
anyway — at ``begin``, at every ``enter`` and at ``end`` (a launch's own close
polls the program *before* it; the one it issued is polled from the next
clock read on) — until it has said "done"; a poll may only observe (never ``block_until_ready``,
never a sleep; one that raises is dropped).  Two stamps are kept for the
device: ``t_busy``, the newest clock read at which it was known to hold work
(a poll that said "not done", the return of a readback that blocked, the open
of the dispatch phase that issued the newest program: it starts no earlier),
and ``t_done``, the oldest clock read at which the newest program was seen
finished (a poll that said "done", or the return of its readback).  At the
close of a ``dispatch`` phase (open ``t_d0``, close ``t_d1``) the launch is
booked: the program before it not seen done by ``t_d1`` — the device had work
queued throughout, ``launches_total`` += 1 and no more; seen done, or nothing
in flight (the serial step, after a drain) — a **starved launch**:
``starved_launches_total`` += 1, ``device_wait_lo_seconds_total`` +=
max(0, ``t_d0`` − ``t_done``) (a true lower bound: the program finished no
later, the next cannot start before its jitted call begins) and
``device_wait_hi_seconds_total`` += ``t_d1`` − ``t_busy`` (short only of the
runtime's enqueue-to-start latency: ``benchmarks/device_wait_check.py``
measures it from a profile).  **Idle is not waiting**: neither reaches back
beyond the ``begin`` of the first step after one that ended with nothing
issued and nothing in flight (the engine thread sleeps between the two).
While :meth:`profiling`, the ``dyn.*`` event of the phase that opens next
carries the launch's ``dev_wait_lo_us`` / ``dev_wait_hi_us`` (0 when not
starved), so a kept profile holds the bracket beside the real gap on the
``XLA Modules`` line.  What it cannot see: the
enqueue-to-start latency inside ``hi``, a wait shorter than the distance
between two polls (``lo`` reads 0), and, across chips, the exposed share of a
collective (the device is busy, waiting for its peers).  At the open of a
``readback`` the dispatch about to be read is polled once more:
done already → ``<class>_ready_readbacks_total`` += 1 for the class of the
*step* — the turns in which the host had no slack at all.  In a turn that
issued ahead that is the look the launch's close took, so there the ready
readbacks are the starved launches told apart by class (the launch counters
are kept as totals only); the two differ by the serial steps, whose launch
is starved by rule and whose readback blocks.

The headline derived number of the host's side is
**host_gap_ms_per_turn** — wall time per dispatching step spent *outside*
dispatch+readback: the host's own work per step.  While a dispatch is in
flight that time runs under the device program (hidden), and it is device
time lost only in the steps that read back first.  The aggregates are
always on: per busy step about twenty clock reads, at most nine polls, two
small dicts and a handful of float adds; per-step *spans* of the dtspan
plane are emitted only when that plane is enabled.

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
(**launch**: what it costs the host to hand the device its next program;
the ``upload`` alone beside it, so that launch − upload is the jitted call)
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
           "CLASS_KEYS", "KIND_CLASS"]

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
# the snapshot's ``<class>_<key>`` entries (``class_totals``), and which of
# them are counts
CLASS_KEYS = (
    "steps_total", "wall_seconds_total", "device_seconds_total",
    "launch_seconds_total", "upload_seconds_total", "readback_seconds_total",
    "ready_readbacks_total",
)
_COUNTS = {k for k in CLASS_KEYS if not k.endswith("seconds_total")}
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
    monitoring).  Two engines in one process interleave their steps on it:
    the numbers are then of neither, but no interleaving may raise, so what
    another thread can empty between a test and a use is read once into a
    local (``_flight`` is a tuple, replaced and never changed in place)."""

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
        # device-facing seconds (dispatch -> readback returned) split by
        # jitted-entrypoint kind — the denominator of the dtperf gauge
        self.dispatch_kind_s: dict[str, float] = {}
        self.dispatch_kind_n: dict[str, int] = {}
        # by class, under the snapshot's ``<class>_<key>``: busy steps by
        # what they dispatched (count, wall, device-facing, launch with
        # its upload, readback and how many found their dispatch done)
        self.class_totals = {k: dict.fromkeys(CLASSES, 0 if k in _COUNTS
                                              else 0.0) for k in CLASS_KEYS}
        # every launch, those before which the device had run dry, and
        # for how long at least and at most
        self.launches_total = 0
        self.starved_launches_total = 0
        self.device_wait_lo_s_total = 0.0
        self.device_wait_hi_s_total = 0.0
        # the device, watched from the host: un-read dispatches oldest
        # first as [probe, seen done]; the probe in_flight() handed for the
        # one the open dispatch phase issues; t_busy / t_done / t_cut of
        # the module docstring (t_done None: the newest is not known done)
        self._flight: tuple = ()
        self._issued_probe: Optional[Callable[[], bool]] = None
        self._t_busy = 0.0
        self._t_done: Optional[float] = 0.0
        self._t_cut = 0.0
        self._t_d0 = 0.0
        self._no_work = True
        self._step_ready = 0
        self._wait_args: Optional[dict] = None
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
        self._step_ready = 0
        if self._no_work:
            # idle is not waiting: the step before had nothing to run
            self._t_cut = now
        self._observe(now)
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
        now = self._clock()
        launched = self._phase == "dispatch"
        self._leave(now)
        if not launched:
            self._observe(now)
        self._close(now)
        if carried is not None:
            self._carried = carried
        if kind is not None:
            self._kind = kind
            if issued:
                self._issued.add(kind)
                self.dispatch_kind_n[kind] = \
                    self.dispatch_kind_n.get(kind, 0) + 1
        if phase == "dispatch":
            self._t_d0 = now
        elif phase == "readback":
            flight = self._flight
            if launched and len(flight) == 1:
                self._observe(now)  # what is read is what was just issued
            if flight and (flight[0][1] or self._t_done is not None):
                self._step_ready += 1   # the host had no slack this turn
        self._open(phase)

    def in_flight(self, probe: Optional[Callable[[], bool]]) -> None:
        """The dispatch that the open ``dispatch`` phase issued stays
        un-read for now, and ``probe()`` says without blocking whether it
        has finished (``jax.Array.is_ready`` of one of its outputs).
        ``None``: the engine dropped what it had un-read (``fail_all``)."""
        if probe is None:
            self._flight = ()
        elif self._t0 is not None and self._phase == "dispatch":
            self._issued_probe = probe

    # ------------------------------------------- the device, from the host
    def _observe(self, now: float) -> None:
        """One look, at the clock read ``now``, at the newest program
        issued — unless it has been seen finished already.  A probe may
        only observe; one that raises is dropped (its program then counts
        as running until it is read back)."""
        flight = self._flight
        if self._t_done is not None or not flight:
            return
        entry = flight[-1]
        if entry[0] is None:
            return
        try:
            done = entry[0]()
        except Exception:
            entry[0] = None  # monitoring must never break the step loop
            return
        if done:
            entry[1] = True
            self._t_done = now
        else:
            self._t_busy = now

    def _leave(self, now: float) -> None:
        """The open phase closes at ``now``: a ``dispatch`` phase has
        launched a program, a ``readback`` has returned."""
        if self._phase == "dispatch":
            self._observe(now)
            self._launched(now)
        elif self._phase == "readback":
            flight = self._flight
            self._flight = flight[1:]
            if not flight or not flight[0][1]:
                self._t_busy = now      # it blocked: work until now
            if len(flight) < 2 and self._t_done is None:
                self._t_done = now      # the newest program has been read

    def _launched(self, now: float) -> None:
        """Book the launch whose ``dispatch`` phase ran ``_t_d0`` .. ``now``
        (module docstring, "Did the device wait?")."""
        self.launches_total += 1
        lo = hi = 0.0
        t_done = self._t_done
        if t_done is not None:
            # the device had nothing queued: it waited for this launch
            lo = max(0.0, self._t_d0 - max(t_done, self._t_cut))
            hi = now - max(self._t_busy, self._t_cut)
            self.starved_launches_total += 1
            self.device_wait_lo_s_total += lo
            self.device_wait_hi_s_total += hi
        self._wait_args = ({"dev_wait_lo_us": round(lo * 1e6, 1),
                            "dev_wait_hi_us": round(hi * 1e6, 1)}
                           if self._annotation.is_enabled() else None)
        # the program just issued is the newest now; the engine keeps at
        # most two un-read, and then only until the older is read
        self._flight = self._flight[-1:] + ([self._issued_probe, False],)
        self._issued_probe = None
        self._t_done = None
        self._t_busy = max(self._t_busy, self._t_d0)  # it starts no earlier

    def _open(self, phase: str) -> None:
        self._phase = phase
        if self._annotation.is_enabled():
            kind = self._kind if phase in _DEVICE_FACING else None
            carried = self._carried if phase in _DISPATCH_PHASES else {}
            # the launch that closed last, on the event that follows it
            wait, self._wait_args = self._wait_args or {}, None
            span = self._annotation(
                _SPAN_NAMES[phase], step=self.busy_steps_total,
                kind=kind or "", t_mono_ns=time.monotonic_ns(), **carried,
                **wait)
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
        t0 = self._t0
        if t0 is None:
            return
        now = self._clock()
        launched = self._phase == "dispatch"
        self._leave(now)
        if not launched:
            self._observe(now)
        self._close(now)
        phases = self._phases
        wall = now - t0
        t0_ns = self._t0_ns
        self._t0 = None
        busy = any(phases.get(p) for p in _DISPATCH_PHASES)
        self._no_work = not busy and not self._flight
        self.steps_total += 1
        if not busy:
            return  # idle polls would drown the per-turn numbers
        facing = sum(phases.get(p, 0.0) for p in _DEVICE_FACING)
        gap = wall - facing
        self.busy_steps_total += 1
        self.wall_s_total += wall
        self.host_gap_s_total += gap
        # list(): a second engine's step may add to these meanwhile
        for p, v in list(phases.items()):
            self.phase_s_total[p] = self.phase_s_total.get(p, 0.0) + v
        classes = {KIND_CLASS.get(k, "mixed")
                   for k in list(self._issued or self._step_kinds)}
        cls = classes.pop() if len(classes) == 1 else "mixed"
        upload = phases.get("upload", 0.0)
        for key, v in (
                ("steps_total", 1), ("wall_seconds_total", wall),
                ("device_seconds_total", facing),
                ("launch_seconds_total", upload + phases.get("dispatch", 0.0)),
                ("upload_seconds_total", upload),
                ("readback_seconds_total", phases.get("readback", 0.0)),
                ("ready_readbacks_total", self._step_ready)):
            self.class_totals[key][cls] += v
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
            "phases": {p: self.phase_s_total.get(p, 0.0) for p in PHASES},
            "launches_total": self.launches_total,
            "starved_launches_total": self.starved_launches_total,
            "device_wait_lo_seconds_total": self.device_wait_lo_s_total,
            "device_wait_hi_seconds_total": self.device_wait_hi_s_total,
            # flat, so that a reader of top-level numbers gets them
            **{f"{c}_{k}": by_class[c]
               for k, by_class in self.class_totals.items() for c in CLASSES},
            "dispatch_kinds": {
                k: {
                    "seconds": self.dispatch_kind_s[k],
                    "count": self.dispatch_kind_n.get(k, 0),
                }
                for k in sorted(self.dispatch_kind_s)
            },
        }


step_timeline = StepTimeline()
