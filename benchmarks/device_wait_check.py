"""Did the device wait?  The engine's own answer against the device trace.

The step timeline (``dynamo_tpu/obs/timeline.py``) books, at every launch, a
lower and an upper figure for how long the chip had stood with nothing queued
(``device_wait_lo/hi_seconds_total``), from non-blocking polls of the dispatch
in flight and no profiler.  While a profiler session is open the same two
figures ride on the ``dyn.*`` event that follows the launch's ``dyn.dispatch``
(``dev_wait_lo_us`` / ``dev_wait_hi_us``), so a kept profile holds both the
bracket and the truth: the gap on the device's ``XLA Modules`` line before the
program that launch started.  This reads one such profile and prints, per
launch of the slice, gap against bracket; the share of launches whose gap lies
inside [lo - 0.2 ms, hi + latency + the 20 us under which a gap is nobody's];
and the enqueue-to-start latency it saw (program start - return of the jitted
call, over the starved launches: what ``hi`` is short of; beside it program
start - the call's begin, which is what a dry device waits once the host has
got as far as the call).

    chiprun -- sh -c 'python3 -m cellbench.run --workload <cell> --seed 7 \\
        --seconds 51 --trace 1 --keep-trace chiprun_out/t < /dev/null && \\
        python3 benchmarks/device_wait_check.py chiprun_out/t \\
        --out chiprun_out/t/wait.json'

No jax: the file is read with cellbench's wire-format walk (the Python tracer
writes hundreds of thousands of host events).  The slice is traced with the
Python tracer on, so its turns are slower than the window's; bracket and gap
are of the same traced turns, which is all the comparison needs.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cellbench.readers import tracefile as tf  # noqa: E402

# dispatch kind -> the program's name on the XLA Modules line
MODULE_OF = {"step": "jit__step_impl", "decode_multi": "jit__multi_impl",
             "spec_verify": "jit__spec_impl", "sp_prefill": "jit__sp_impl",
             "prefill_ragged": "jit__ragged_impl",
             "unified": "jit__unified_impl"}
SLACK_US = 200.0        # below lo: the two clocks, and a poll's own length
FAR_US = 1000.0         # a launch outside by more is printed by itself
CLOCKS_US = 2000.0      # a profile's host and device lines agree no better


def load(path: str, plane: str = r"^/device:TPU:\d+$"):
    """(launches, modules) of one ``.xplane.pb``: a launch is a
    ``dyn.dispatch`` event with the bracket its follower carried —
    {step, kind, t0, t1 (ns on the trace's axis), lo_us, hi_us} — and
    ``modules`` the [name, start_ns, dur_ns] of the first device's
    ``XLA Modules`` line, in order."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    plane_re = re.compile(plane)
    devices: dict[str, list] = {}
    launches: list = []
    for f, _, raw in tf._fields(space):
        if f != 1:
            continue
        p = tf._plane(raw)
        names = p["stat_names"]
        if plane_re.search(p["name"]):
            for raw_line in p["lines"]:
                lname, t0, events = tf._line(raw_line)
                if not tf.MODULE_LINE.search(lname):
                    continue
                rows = devices.setdefault(p["name"], [])
                for ev in events:
                    mid, off, dur, _ = tf._event(ev)
                    if dur > 0:
                        rows.append([p["events"].get(mid, (str(mid),))[0],
                                     t0 + off / 1e3, dur / 1e3])
        if p["name"].startswith("/host:"):
            ids = {mid for mid, (mname, _) in p["events"].items()
                   if mname.startswith(tf.SPAN_PREFIX)}
            for raw_line in p["lines"] if ids else ():
                _, t0, events = tf._line(raw_line)
                spans = []
                for ev in events:
                    if len(ev) < 2 or ev[0] != 0x08:
                        continue
                    mid, _ = tf._varint(ev, 1)
                    if mid not in ids:
                        continue
                    mid, off, dur, stats = tf._event(ev)
                    spans.append((t0 + off / 1e3, dur / 1e3,
                                  p["events"][mid][0],
                                  dict(tf._stat(s, names) for s in stats)))
                spans.sort(key=lambda s: s[0])
                found = []
                for before, (_, _, _, st) in zip(spans, spans[1:]):
                    if before[2] == "dyn.dispatch" and "dev_wait_lo_us" in st:
                        found.append({
                            "step": int(before[3].get("step") or 0),
                            "kind": before[3].get("kind") or "",
                            "t0": before[0], "t1": before[0] + before[1],
                            "lo_us": float(st["dev_wait_lo_us"]),
                            "hi_us": float(st["dev_wait_hi_us"])})
                if len(found) > len(launches):
                    launches = found        # the engine's thread
    first = min(devices) if devices else None
    return launches, sorted(devices.get(first, []), key=lambda m: m[1])


def check(launches: list, modules: list) -> dict:
    """Pair each launch with the program it started, and lay the gap before
    that program over the launch's bracket.  The device runs programs in the
    order they were issued, so launch i started serving program i + s, where
    s is how many programs the slice's first launch found already issued:
    the smallest shift under which every program has its launch's kind and
    starts after its launch's jitted call began — to within the ~1 ms by
    which one profile's host and device lines may disagree (seen from -0.9
    to +0.3 ms, PERF.md section 6, PR 58: the gap is device time alone and
    the bracket host time alone, so only the latency carries that skew)."""
    serving = [i for i, m in enumerate(modules)
               if m[0].startswith(tuple(MODULE_OF.values()))]

    def misfits(shift):
        return sum(
            not modules[k][0].startswith(MODULE_OF.get(ln["kind"], "jit_"))
            or modules[k][1] < ln["t0"] - CLOCKS_US * 1e3
            for ln, k in zip(launches, serving[shift:]))

    shift = min(range(4), key=lambda sh: (misfits(sh), sh))
    rows = []
    for ln, k in zip(launches, serving[shift:]):
        if k == 0:
            continue                    # nothing before it to be idle after
        name, start, _ = modules[k]
        ended = modules[k - 1][1] + modules[k - 1][2]
        rows.append({**ln, "module": name,
                     "gap_us": max(0.0, (start - ended) / 1e3),
                     "start_after_call_us": (start - ln["t0"]) / 1e3,
                     "start_after_return_us": (start - ln["t1"]) / 1e3,
                     "starved": ln["hi_us"] > 0})
    starved = [r for r in rows if r["starved"]]
    lat = sorted(max(0.0, r["start_after_return_us"]) for r in starved)
    latency = statistics.median(lat) if lat else 0.0
    # two programs queued back to back still stand some microseconds apart
    # on the device; under 20 us a gap is not the host's (``tracefile``'s
    # rule for ``device.idle_pct``), so it is no wait here either
    floor = tf.MIN_GAP_NS / 1e3
    queued = sorted(r["gap_us"] for r in rows if not r["starved"])

    def quantile(q):
        return lat[min(len(lat) - 1, int(q * len(lat)))] if lat else 0.0

    for r in rows:
        r["below_us"] = max(0.0, r["lo_us"] - r["gap_us"])
        r["above_us"] = max(0.0, r["gap_us"] - r["hi_us"] - latency - floor)
        r["inside"] = r["below_us"] <= SLACK_US and r["above_us"] <= 0.0
    n = len(rows)
    return {"rows": rows, "summary": {
        "launches": n, "starved": len(starved), "shift": shift,
        "misfits": misfits(shift),
        "inside_pct": 100.0 * sum(r["inside"] for r in rows) / n if n else None,
        "inside_hi_only_pct":       # with no latency allowed above hi
            100.0 * sum(r["below_us"] <= SLACK_US
                        and r["gap_us"] <= r["hi_us"] for r in rows) / n
            if n else None,
        "latency_us": {"median": latency, "p95": quantile(0.95),
                       "max": lat[-1] if lat else 0.0},
        "queued_gap_us": {"median": statistics.median(queued),
                          "max": queued[-1]} if queued else None,
        "start_after_call_us_starved_median": statistics.median(
            r["start_after_call_us"] for r in starved) if starved else None,
        "start_after_call_us_min": min(
            (r["start_after_call_us"] for r in rows), default=None),
        "gap_us_sum": sum(r["gap_us"] for r in rows),
        "lo_us_sum": sum(r["lo_us"] for r in rows),
        "hi_us_sum": sum(r["hi_us"] for r in rows),
        "far": [r for r in rows
                if r["below_us"] > FAR_US or r["above_us"] > FAR_US]}}


def poll_cost(n: int = 50_000) -> dict:
    """What one poll costs, on whatever devices jax sees here (the one
    place this file imports jax): microseconds per ``is_ready()`` of a
    small array replicated over all of them, once it is ready and while
    the program that makes it still runs."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    rep = NamedSharding(Mesh(np.array(devs), ("x",)), PartitionSpec())

    @jax.jit
    def spin(a):                        # tens of ms of a chip
        a = jax.lax.fori_loop(0, 400, lambda _, x: jnp.tanh(x @ x), a)
        return a[0, :64].astype(jnp.int32)

    a = jax.device_put(jnp.ones((2048, 2048), jnp.bfloat16), rep)
    out = spin(a).block_until_ready()
    t = time.perf_counter()
    for _ in range(n):
        out.is_ready()
    ready_us = (time.perf_counter() - t) / n * 1e6
    out = spin(a)
    polls, t = 0, time.perf_counter()
    while not out.is_ready():
        polls += 1
    running_s = time.perf_counter() - t
    return {"devices": len(devs), "platform": devs[0].platform,
            "ready_us": ready_us, "polls_while_running": polls,
            "running_us": running_s / max(polls, 1) * 1e6,
            "program_s": running_s}


def main(argv=None) -> int:
    if argv is None and sys.argv[1:] == ["--poll-cost"]:
        print("# poll cost: " + json.dumps(poll_cost()))
        return 0
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("profile", help="an .xplane.pb, or a directory holding one "
                   "(or --poll-cost alone: what one is_ready() costs here)")
    p.add_argument("--plane", default=r"^/device:TPU:\d+$",
                   help="regular expression of the device planes")
    p.add_argument("--out", default=None, help="write rows and summary here")
    p.add_argument("--rows", type=int, default=40,
                   help="launches to print (all go to --out)")
    a = p.parse_args(argv)
    path = a.profile
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    launches, modules = load(path, a.plane)
    if not launches:
        raise SystemExit("no dyn.* event of the profile carries "
                         "dev_wait_lo_us: a program from before PR 58?")
    out = check(launches, modules)
    print(f"# {path}: {len(launches)} launches, {len(modules)} programs")
    print("# step kind            gap_us     lo_us     hi_us  "
          "start-return_us  inside")
    for r in out["rows"][:a.rows]:
        print(f"{r['step']:6d} {r['kind']:14s} {r['gap_us']:9.1f} "
              f"{r['lo_us']:9.1f} {r['hi_us']:9.1f} "
              f"{r['start_after_return_us']:16.1f}  "
              f"{'yes' if r['inside'] else 'NO'}")
    print("# summary: " + json.dumps(out["summary"]))
    if a.out:
        with open(a.out, "w") as f:     # with what check() took, to redo it
            json.dump({**out, "launches": launches, "modules": modules}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
