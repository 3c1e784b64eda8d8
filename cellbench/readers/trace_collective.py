"""Device time spent in collectives, from the traced slice.

A collective is an event of the ``XLA Ops`` line whose HLO name is one of
``all-reduce``, ``all-gather``, ``reduce-scatter``, ``all-to-all`` or
``collective-permute``, alone or in its asynchronous ``-start`` / ``-done``
forms (the part of an asynchronous collective that runs under other
operations is not the device waiting, and is not counted: time is *self*
time, as everywhere in these readers).  On a device the duration of a
collective is the transfer *and* the wait for the slowest peer, which is
what the step pays.

``what``:

``share``        collective self time / busy self time, both summed over the
                 devices, in percent
``per_program``  collective milliseconds per execution of the programs whose
                 module name matches ``program`` (``jit__multi_impl(<id>)``
                 on the ``XLA Modules`` line, ``jit__multi_impl`` on an
                 operation): per device the collective time inside those
                 programs over their executions, mean over the devices

A program that is not sharded has no collective and reads 0: its devices
spend none of their time in one.  (``BENCHMARK.json`` lists the four-chip
cell on the three metrics, so the one-chip cells do not report that zero.)
None when the run made no profile or the profile holds no device operation,
and for ``per_program`` when no matching program ran in the slice, as
``trace_module_ms`` has it.
"""

import re

from cellbench import spec, trace_reduce

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$")
_MARK = "\tcollective\t"


def per_device(t: dict, program: re.Pattern | None = None) -> list[tuple[float, float, int]]:
    """(collective seconds, busy seconds, executions) of each device, the
    first two restricted to operations of programs matching ``program``."""
    out = []
    for dev in t["devices"].values():
        rows = [[(_MARK if COLLECTIVE.match(r[0]) else "\tother\t") + r[4],
                 r[1], r[2]] for r in dev["ops"]]
        coll = busy = 0.0
        for tag, sec in trace_reduce.self_times(rows).items():
            if program is not None and not program.search(tag.rsplit("\t", 1)[1]):
                continue
            busy += sec
            if tag.startswith(_MARK):
                coll += sec
        runs = sum(1 for name, _, _ in dev["modules"]
                   if program is None or program.search(name))
        out.append((coll, busy, runs))
    return out


def reading(t: dict | None, args: dict):
    """The metric from a parsed profile (``tracefile.parse``'s result)."""
    if not t or not any(dev["ops"] for dev in t["devices"].values()):
        return None
    if args["what"] == "share":
        devs = per_device(t)
        busy = sum(b for _, b, _ in devs)
        return 100.0 * sum(c for c, _, _ in devs) / busy if busy else None
    if args["what"] == "per_program":
        devs = [(c, runs) for c, _, runs in
                per_device(t, re.compile(args["program"])) if runs]
        if not devs:
            return None
        return sum(c / runs for c, runs in devs) / len(devs) * 1e3
    raise ValueError(f"unknown reading {args['what']!r}")


def read(ctx: dict, args: dict):
    tracefile = spec.load_module(ctx["root"], "readers", "tracefile")
    return reading(tracefile.for_run(ctx), args)
