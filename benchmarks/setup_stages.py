"""A benchmark run's set-up by stage: ``cellbench.run`` with jax's trace,
lowering, compile and compile-cache events summed per stage of the set-up
(start / warm_up / check / load) and the engine's prefill programs read
after the warm-up and after the check.

``setup_s`` wanders a few per cent between runs of one tree; the lowering and
cache-load seconds and the program counts repeat (PERF.md §6, PRs 55 and 57),
so this is how a change to what a start builds is read.  The run is the
benchmark's own (its result line goes to stdout as ever); one more line,
``# stages: {...}``, goes to stderr, and with ``--out FILE`` into a file:

    chiprun -- python3 benchmarks/setup_stages.py --out chiprun_out/s.json \\
        --workload zaya1-8b.reason-long-closed --seed 7 --seconds 51 --trace 0

``events``: stage -> event -> [count, seconds].  ``jaxpr_trace_duration``
nests (a traced function's inner jits are counted again), so read its count,
not its sum; ``jaxpr_to_mlir_module_duration`` is one a lowered module,
``cache_retrieval_time_sec`` one a load from the persistent cache,
``gc_gen<n>`` CPython's collections of that generation.  ``marks``: each
stage's seconds, the heap at its end (objects frozen and tracked) and the
prefill programs; ``marks.window``: what the program counted inside the
window (the step timeline's and the engine's counters, after − before),
which an untraced run prints nowhere else.  Run it
from the root of the checkout it should measure: a parent commit unpacked
elsewhere is measured by this file run from there.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

PREFILL_ENTRY_POINTS = ("_step_fn", "_ragged_fn", "_unified_fn")


class Stages:
    """The stage the run is in, what jax reported during each, and what the
    wrapped calls took."""

    def __init__(self) -> None:
        self.now = "start"
        self.events: dict[str, dict[str, list]] = {}
        self.marks: dict = {}
        self._gc_began = 0.0

    def on_gc(self, phase: str, info: dict) -> None:
        """CPython's collections by stage and generation, as events
        ``gc_gen<n>``: the engine freezes the heap after every build
        (``engine/async_engine.py::settle_heap``), so what a full collection
        walks inside the window depends on when the last build was."""
        if phase == "start":
            self._gc_began = time.monotonic()
        else:
            self.on_event(f"gc_gen{info['generation']}",
                          time.monotonic() - self._gc_began)

    def on_event(self, event: str, duration: float, **kw) -> None:
        count = self.events.setdefault(self.now, {}).setdefault(
            event.rsplit("/", 1)[-1], [0, 0.0])
        count[0] += 1
        count[1] += duration

    def staged(self, name: str, fn, after=None, of_result=None):
        """``fn`` (a coroutine function) as the stage ``name``: timed, and
        ``after(*its arguments)`` recorded when it ends, ``of_result(what it
        returned)`` under that function's name."""
        async def run(*args, **kw):
            self.now = name
            t = time.monotonic()
            try:
                out = await fn(*args, **kw)
                if of_result is not None:
                    self.marks[of_result.__name__] = of_result(out)
                return out
            finally:
                self.marks[name + "_s"] = time.monotonic() - t
                self.marks[name + "_heap"] = {
                    "frozen": gc.get_freeze_count(),
                    "tracked": len(gc.get_objects())}
                if after is not None:
                    self.marks[name + "_programs"] = after(*args)
                self.now = "between"
        return run

    def report(self) -> dict:
        return {"marks": self.marks,
                "events": {stage: {k: [n, round(s, 3)]
                                   for k, (n, s) in sorted(ev.items())}
                           for stage, ev in self.events.items()}}


def prefill_programs(served, *_) -> dict:
    """What the engine's prefill entry points hold, by their jit caches (a
    tree before PR 57 has no ``prefill_programs_total``: None there)."""
    core = served.core
    held = {name: getattr(core, name)._cache_size()
            for name in PREFILL_ENTRY_POINTS}
    metrics = core.metrics()
    return {**held,
            "prefill_programs_total": metrics.get("prefill_programs_total"),
            "prefill_dispatches_total": metrics.get("prefill_dispatches_total")}


def window(phase: dict) -> dict:
    """What the program counted inside the window: after − before of every
    ``timeline.*`` and ``core.*`` number both edges hold.  An untraced run's
    result line carries only the client's metrics; the engine's own
    (a turn's phases by class, did the device wait: PERF.md §3) are these,
    and under ``--trace 1`` they are the traced run's, whose profiler slows
    the host well beyond its 2 s slice (PERF.md §6, PR 58)."""
    before, after = phase["edges"]
    return {k: after[k] - before[k] for k in sorted(after)
            if k.startswith(("timeline.", "core.")) and k in before}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=None, help="also write the line here")
    a, rest = p.parse_known_args(argv)
    sys.path.insert(0, os.getcwd())       # the checkout this is run from

    import jax

    import cellbench.run as bench

    stages = Stages()
    jax.monitoring.register_event_duration_secs_listener(stages.on_event)
    gc.callbacks.append(stages.on_gc)
    bench.server.start = stages.staged("start", bench.server.start)
    bench.warm_up = stages.staged("warm_up", bench.warm_up, prefill_programs)
    bench.check.run = stages.staged("check", bench.check.run, prefill_programs)
    bench.load_phase = stages.staged("load", bench.load_phase,
                                     of_result=window)
    rc = bench.main(rest)
    line = json.dumps({"argv": rest, **stages.report()})
    print("# stages: " + line, file=sys.stderr, flush=True)
    if a.out:
        os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
        with open(a.out, "w") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
