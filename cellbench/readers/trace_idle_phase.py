"""Device idle time of the traced slice that falls under given phases of the
engine step, as a share of the slice.

The gaps are those of ``device.idle_pct`` (between the merged ``XLA Ops``
intervals of the first device, 20 us and longer).  The phases are the
``dyn.<phase>`` spans the engine writes on its own thread
(dynamo_tpu/obs/timeline.py), matched by *name*, so the reading is the same
with the profiler's Python tracer on or off.  ``spans`` lists the span names
to add up; an empty list stands for the time under no span at all, between
two steps.  The shares of all spans and of no span add up to
``device.idle_pct`` less the gaps under 20 us.  None when the trace holds no
``dyn.*`` span (a program from before PR 25)."""

from cellbench import spec


def read(ctx: dict, args: dict):
    tracefile = spec.load_module(ctx["root"], "readers", "tracefile")
    t = tracefile.for_run(ctx)
    if not t:
        return None
    idle, w = tracefile.idle_by_span(t), tracefile.window(t)
    if idle is None or not w:
        return None
    seconds = sum(idle.get(name, 0.0) for name in args["spans"] or [""])
    return 100.0 * seconds / ((w[1] - w[0]) / 1e9)
