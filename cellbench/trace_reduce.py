"""From a profiler trace to numbers: device busy and idle time, time per
operation name, and the idle gaps by what the host was doing in them.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but jax.  ``load`` turns
it into plain rows — per device a list of ``[name, start_ns, duration_ns]``,
and the same for one host thread — and ``reduce`` works on those rows alone,
so a test can hand it a small recorded trace (``rows`` as JSON).

Which planes and lines are a device's operations is data (``settings.json``,
``trace.device``): on a TPU the plane ``/device:TPU:<n>`` and its line
``XLA Ops``.  Operations on that line nest (a ``while`` spans its body), so
time per name is *self* time — an operation's duration less what its
children cover — and busy time is the union of the intervals.

    python -m cellbench.trace_reduce --dump <xplane.pb>   # look by hand
"""

from __future__ import annotations

import glob
import os
import re
import sys
from collections import defaultdict


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def op_name(text: str) -> str:
    """The TPU's ``XLA Ops`` line names an operation by its whole HLO text,
    ``%fusion.190 = bf16[32,14336]{...} fusion(...)``: keep ``fusion.190``."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path: str, device: dict, host_event: str | None = None) -> dict:
    """{"devices": {plane name: rows}, "host": rows}.  ``device`` holds the
    regular expressions ``plane`` and ``line``.  The host thread whose events
    explain the gaps is the engine's; the profiler does not carry Python's
    thread names, so it is the host line with most events matching
    ``host_event`` (the engine's step function)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    plane_re, line_re = re.compile(device["plane"]), re.compile(device["line"])
    host_re = re.compile(host_event) if host_event else None
    devices: dict[str, list] = {}
    host, host_hits = [], 0
    for plane in data.planes:
        if plane_re.search(plane.name):
            for line in plane.lines:
                if not line_re.search(line.name):
                    continue
                # a backend without device planes (the CPU, in rehearsals)
                # runs its programs on threads of the host plane, several at
                # once with a dispatch in flight: the matching lines of one
                # plane are one device, busy while any of them is
                devices.setdefault(plane.name, []).extend(
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events if e.duration_ns > 0)
        if host_re is not None and plane.name.startswith("/host:"):
            for line in plane.lines:
                rows = [[e.name, e.start_ns, e.duration_ns]
                        for e in line.events if e.duration_ns > 0]
                hits = sum(1 for r in rows if host_re.search(r[0]))
                if hits > host_hits:
                    host, host_hits = rows, hits
    return {"devices": devices, "host": host}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(rows: list) -> dict[str, float]:
    """Seconds per name, each event counted less the events nested in it."""
    total: dict[str, float] = defaultdict(float)
    stack: list[list] = []   # [name, end, self_ns]
    for name, start, dur in sorted(rows, key=lambda r: (r[1], -r[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            total[done[0]] += done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for done in stack:
        total[done[0]] += done[2]
    return {k: max(v, 0.0) / 1e9 for k, v in total.items()}


def flatten(host_rows: list, depth: int) -> list[tuple[float, float, str]]:
    """The host thread as a flat sequence of (start, end, name): at every
    moment the name of the deepest event no deeper than ``depth`` (0 = the
    thread's outermost events).  A fixed depth keeps the vocabulary to the
    engine's own phases instead of whatever leaf happened to run."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []
    cur = 0.0

    def emit(t: float) -> None:
        nonlocal cur
        if stack and t > cur:
            segs.append((cur, t, stack[-1][0]))
        cur = t

    for name, start, dur in sorted(host_rows, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        if len(stack) > depth:
            continue        # deeper than asked for: inside the visible event
        emit(start)
        stack.append((name, start + dur))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def attribute(gaps: list[tuple[float, float]], segs: list) -> dict[str, float]:
    """Seconds of the gaps by the host segment they overlap; what overlaps
    none is the time between two steps of the engine."""
    out: dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k, covered = j, 0.0
        while k < len(segs) and segs[k][0] < g1:
            o = min(g1, segs[k][1]) - max(g0, segs[k][0])
            if o > 0:
                out[segs[k][2]] += o / 1e9
                covered += o
            k += 1
        if g1 - g0 - covered > 0:
            out["(between engine steps)"] += (g1 - g0 - covered) / 1e9
    return out


def matching(times: dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in times.items() if rx.search(k))


def reduce(rows: dict, gap_depth: int = 3, top: int = 10) -> dict:
    """window_s, busy_s (mean over devices), idle share, seconds per
    operation name (mean over devices) and the idle gaps by host activity
    (first device; the devices of one program idle together)."""
    devices = rows["devices"]
    if not devices:
        return {}
    starts = [r[1] for d in devices.values() for r in d]
    ends = [r[1] + r[2] for d in devices.values() for r in d]
    w0, w1 = min(starts), max(ends)
    busy, per_name = [], defaultdict(float)
    gaps_by: dict[str, float] = {}
    for i, d in enumerate(devices.values()):
        merged = union([(r[1], r[1] + r[2]) for r in d])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, sec in self_times(d).items():
            per_name[name] += sec / len(devices)
        if i == 0:
            gaps = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
                    if s1 - e0 >= 20_000]   # 20 us: below that, not the host
            gaps_by = attribute(gaps, flatten(rows.get("host", []), gap_depth))
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy) / len(busy)
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "op_seconds": dict(per_name),
            "breakdown": {"device_ops": rank(per_name),
                          "idle_gaps": rank(gaps_by)}}


def dump(path: str) -> None:
    """Planes, lines and the names that took most time: for reading one
    trace by hand before writing a pattern against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            by: dict[str, list] = defaultdict(lambda: [0, 0.0])
            n, first, last = 0, None, 0.0
            for e in line.events:
                n += 1
                by[e.name][0] += 1
                by[e.name][1] += e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
            print(f"  LINE {line.name!r}: {n} events, "
                  f"{first} .. {last} ns")
            for name, (cnt, ns) in sorted(by.items(), key=lambda kv: -kv[1][1])[:25]:
                print(f"    {ns / 1e6:12.3f} ms  x{cnt:<7d} {name[:140]}")


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    else:
        print(__doc__)
        sys.exit(2)
