"""The profile of one run, read once: device operations with the model scope
and the program each belongs to, the executions of whole programs, and the
engine's own phases (``dyn.<phase>`` spans, dynamo_tpu/obs/timeline.py).

A helper of the trace readers, not a reader: they get it with
``spec.load_module(root, "readers", "tracefile")`` and call ``for_run(ctx)``,
which parses the run's ``.xplane.pb`` on first use and keeps the result by
path, so seventeen metrics cost one parse.

``jax.profiler.ProfileData`` shows an event's own stats only.  What says
where an operation comes from sits on the event's *metadata*: ``tf_op`` is
the operation's name-stack path (``jit(_multi_impl)/while/body/mlp/dot:``),
``program_id`` the program it was compiled into.  So the file is read here
as what it is, a protocol-buffer ``XSpace`` (tsl/profiler/protobuf/
xplane.proto), with a wire-format walk of the few fields needed and no
generated code.  Times are ``line.timestamp_ns + offset_ps / 1000``, the
axis ``trace_reduce`` uses.

    python3 cellbench/readers/tracefile.py <file.xplane.pb> [tpu|cpu]

prints, for reading a trace by hand, the heaviest operations with scope and
program, device time by scope and by program, and idle time by phase.
"""

from __future__ import annotations

import re
import struct
import sys
from collections import defaultdict

# the scopes dynamo_tpu/models/llama.py and engine/sampling.py put on the
# model; an operation under none of them is "unscoped" (what XLA adds
# around the layer scan: weight slices, layout copies, the loop itself)
SCOPES = ("embed", "attn_proj", "attn", "attn_out", "mlp", "logits", "sample")
# Operations the compiler renames: the TPU's ragged-dot rewrite emits custom
# calls whose metadata says only "ragged-dot-none", dropping the name stack
# (seen by compiling the grouped-expert MLP for a described v5e, PR 25).  The
# one place the model has a ragged dot is the experts' three projections.
COMPILER_NAMED = (("ragged-dot", "mlp"),)
SPAN_PREFIX = "dyn."
MIN_GAP_NS = 20_000     # as trace_reduce.reduce: below that, not the host
MODULE_LINE = re.compile(r"^XLA Modules$")

_CACHE: dict[str, dict] = {}


# --------------------------------------------------------------- wire format
def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, wire type, value) of one message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i:i + size]
            i += size
        elif wire == 1:
            v = b[i:i + 8]
            i += 8
        elif wire == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict[int, str]) -> tuple[str, object]:
    """One XStat -> (name, value); a ref_value is a string kept as a stat
    name."""
    name, value = "", None
    for f, wire, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, "")
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
    return name, value


def _plane(buf) -> dict:
    """name, lines (raw), event metadata id -> (name, raw stats), stat
    names."""
    name, lines, events, stat_names = "", [], {}, {}
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:        # map entry: key = 1, XEventMetadata = 2
            for f2, _, meta in _fields(v):
                if f2 != 2:
                    continue
                mid, mname, mstats = 0, "", []
                for f3, _, x in _fields(meta):
                    if f3 == 1:
                        mid = x
                    elif f3 == 2:
                        mname = _text(x)
                    elif f3 == 5:
                        mstats.append(x)
                events[mid] = (mname, mstats)
        elif f == 5:        # map entry: key = 1, XStatMetadata = 2
            for f2, _, meta in _fields(v):
                if f2 != 2:
                    continue
                sid, sname = 0, ""
                for f3, _, x in _fields(meta):
                    if f3 == 1:
                        sid = x
                    elif f3 == 2:
                        sname = _text(x)
                stat_names[sid] = sname
    return {"name": name, "lines": lines, "events": events,
            "stat_names": stat_names}


def _line(buf) -> tuple[str, int, list]:
    name, t0, events = "", 0, []
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    return name, t0, events


def _event(buf) -> tuple[int, int, int, list]:
    mid = offset_ps = duration_ps = 0
    stats = []
    for f, _, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            offset_ps = v
        elif f == 3:
            duration_ps = v
        elif f == 4:
            stats.append(v)
    return mid, offset_ps, duration_ps, stats


# ------------------------------------------------------------------- parsing
def op_name(text: str) -> str:
    """``%fusion.190 = bf16[...] fusion(...)`` -> ``fusion.190`` (as
    trace_reduce.op_name)."""
    return text.split(" = ", 1)[0].lstrip("%")


def scope_of(path: str) -> str:
    """The outermost known scope on an operation's name-stack path, or ""."""
    for part in path.split("/"):
        if part in SCOPES:
            return part
    for prefix, scope in COMPILER_NAMED:
        if path.startswith(prefix):
            return scope
    return ""


def parse(path: str, device: dict) -> dict:
    """{"devices": {key: {"ops": [[name, start_ns, dur_ns, scope, program]],
    "modules": [[name, start_ns, dur_ns]]}}, "spans": [[name, start_ns,
    dur_ns, step, kind, t_mono_ns]], "scoped": bool}.  ``device`` holds the
    regular expressions ``plane`` and ``line`` of settings.json.  ``spans``
    are the ``dyn.*`` events of the engine thread: the host line with most
    of them.  ``scoped`` says whether any operation carried a known scope
    (a program without named scopes, or a backend without operation
    metadata, has none).  "paths" maps an operation's name to its whole
    name-stack path, for reading by hand."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    plane_re, line_re = re.compile(device["plane"]), re.compile(device["line"])
    devices: dict[str, dict] = {}
    spans: list = []
    scoped = False
    paths: dict[str, str] = {}
    for f, _, raw in _fields(space):
        if f != 1:
            continue
        plane = _plane(raw)
        names = plane["stat_names"]
        if plane_re.search(plane["name"]):
            where: dict[int, tuple[str, str, str]] = {}    # name, scope, program
            programs: dict[str, str] = {}
            for mid, (mname, mstats) in plane["events"].items():
                stats = dict(_stat(s, names) for s in mstats)
                tf_op = str(stats.get("tf_op") or "")
                scope = scope_of(tf_op)
                scoped = scoped or bool(scope)
                where[mid] = (op_name(mname), scope,
                              str(stats.get("program_id") or ""))
                paths[where[mid][0]] = tf_op
            for raw_line in plane["lines"]:
                lname, t0, events = _line(raw_line)
                is_ops = bool(line_re.search(lname))
                if not is_ops and not MODULE_LINE.search(lname):
                    continue
                # the matching lines of one plane are one device (the CPU's
                # client threads, in rehearsals: trace_reduce.load)
                dev = devices.setdefault(plane["name"], {"ops": [], "modules": []})
                for ev in events:
                    mid, off, dur, _ = _event(ev)
                    if dur <= 0:
                        continue
                    name, scope, program = where.get(mid, (str(mid), "", ""))
                    start, dur_ns = t0 + off / 1e3, dur / 1e3
                    if is_ops:
                        dev["ops"].append([name, start, dur_ns, scope, program])
                    else:
                        dev["modules"].append([name, start, dur_ns])
                        m = re.search(r"\((\d+)\)$", name)
                        if m:
                            programs[m.group(1)] = name[:m.start()]
            if plane["name"] in devices:     # program id -> module name
                for row in devices[plane["name"]]["ops"]:
                    row[4] = programs.get(row[4], row[4])
        if plane["name"].startswith("/host:"):
            ids = {mid for mid, (mname, _) in plane["events"].items()
                   if mname.startswith(SPAN_PREFIX)}
            if not ids:
                continue
            for raw_line in plane["lines"]:
                _, t0, events = _line(raw_line)
                rows = []
                for ev in events:
                    # metadata_id is an event's first field: look before
                    # paying for the whole event (the Python tracer writes
                    # hundreds of thousands)
                    if len(ev) < 2 or ev[0] != 0x08:
                        continue
                    mid, _ = _varint(ev, 1)
                    if mid not in ids:
                        continue
                    mid, off, dur, stats = _event(ev)
                    st = dict(_stat(s, names) for s in stats)
                    rows.append([plane["events"][mid][0], t0 + off / 1e3,
                                 dur / 1e3, st.get("step"),
                                 st.get("kind") or "", st.get("t_mono_ns")])
                if len(rows) > len(spans):
                    spans = rows
    spans.sort(key=lambda r: r[1])
    return {"devices": devices, "spans": spans, "scoped": scoped,
            "paths": paths}


def for_run(ctx: dict) -> dict | None:
    """The parsed profile of this run, or None when it made none."""
    from cellbench import spec, trace_reduce

    if not ctx.get("trace_dir"):
        return None
    path = trace_reduce.find_xplane(ctx["trace_dir"])
    if not path:
        return None
    if path not in _CACHE:
        settings = spec.load_settings(ctx["root"])
        platform = (ctx.get("device") or {}).get("platform", "tpu")
        sel = settings["trace"]["device"].get(platform)
        _CACHE[path] = parse(path, sel) if sel else None
    return _CACHE[path]


# ---------------------------------------------------------------- reductions
def window(t: dict) -> tuple[float, float] | None:
    """The traced slice as trace_reduce.reduce takes it: first start to last
    end of the device operations."""
    rows = [r for d in t["devices"].values() for r in d["ops"]]
    if not rows:
        return None
    return min(r[1] for r in rows), max(r[1] + r[2] for r in rows)


def idle_gaps(t: dict) -> list[tuple[float, float]]:
    """The gaps ``device.idle_pct`` is made of: between the merged operation
    intervals of the first device, 20 us and longer."""
    from cellbench import trace_reduce

    first = next(iter(t["devices"].values()), None)
    if not first or not first["ops"]:
        return []
    merged = trace_reduce.union([(r[1], r[1] + r[2]) for r in first["ops"]])
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])
            if s1 - e0 >= MIN_GAP_NS]


def idle_by_span(t: dict) -> dict[str, float] | None:
    """Seconds of the idle gaps by the ``dyn.*`` span open on the engine
    thread; "" is the time under no span, between two steps.  None when the
    program wrote no spans."""
    if not t["spans"]:
        return None
    spans = t["spans"]
    out: dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in idle_gaps(t):
        while j < len(spans) and spans[j][1] + spans[j][2] <= g0:
            j += 1
        k, covered = j, 0.0
        while k < len(spans) and spans[k][1] < g1:
            o = min(g1, spans[k][1] + spans[k][2]) - max(g0, spans[k][1])
            if o > 0:
                out[spans[k][0]] += o / 1e9
                covered += o
            k += 1
        out[""] += max(g1 - g0 - covered, 0.0) / 1e9
    return dict(out)


def scope_seconds(t: dict) -> dict[str, float]:
    """Self time of the device operations by scope ("" = unscoped), summed
    over the devices."""
    from cellbench import trace_reduce

    out: dict[str, float] = defaultdict(float)
    for dev in t["devices"].values():
        by = trace_reduce.self_times([[r[3], r[1], r[2]] for r in dev["ops"]])
        for scope, sec in by.items():
            out[scope] += sec
    return dict(out)


def dump(path: str, platform: str = "tpu") -> None:
    from cellbench import spec, trace_reduce

    sel = spec.load_settings(spec.REPO_ROOT)["trace"]["device"][platform]
    t = parse(path, sel)
    w = window(t)
    if not w:
        print("no device operations")
        return
    span = (w[1] - w[0]) / 1e9
    print(f"slice {span:.4f} s, {len(t['spans'])} dyn spans, "
          f"scoped={t['scoped']}")
    for key, dev in t["devices"].items():
        # self time by (name, scope, program): names repeat across programs
        tagged = [["\t".join((r[0], r[3] or "-", r[4] or "-")), r[1], r[2]]
                  for r in dev["ops"]]
        by_op = trace_reduce.self_times(tagged)
        count: dict[str, int] = defaultdict(int)
        longest: dict[str, float] = defaultdict(float)
        for tag, _, dur in tagged:
            count[tag] += 1
            longest[tag] = max(longest[tag], dur)
        total = sum(by_op.values())
        print(f"DEVICE {key}: busy {total:.4f} s")
        by_prog: dict[str, float] = defaultdict(float)
        for tag, sec in by_op.items():
            by_prog[tag.split("\t")[2]] += sec
        for prog, sec in sorted(by_prog.items(), key=lambda kv: -kv[1]):
            print(f"  program {prog:40s} {sec:9.4f} s {100 * sec / total:6.2f}%")
        for tag, sec in sorted(by_op.items(), key=lambda kv: -kv[1])[:40]:
            name, scope, prog = tag.split("\t")
            print(f"  {sec:9.4f} s {100 * sec / total:6.2f}%  x{count[tag]:<6d} "
                  f"max {longest[tag] / 1e6:8.3f} ms  {scope:10s} {prog:18s} "
                  f"{name:34s} {t['paths'].get(name, '')[-70:]}")
        mods: dict[str, list] = defaultdict(list)
        for name, _, dur in dev["modules"]:
            mods[re.sub(r"\(\d+\)$", "", name)].append(dur / 1e6)
        for name, durs in sorted(mods.items(), key=lambda kv: -sum(kv[1])):
            print(f"  module {name:36s} x{len(durs):<5d} mean "
                  f"{sum(durs) / len(durs):8.3f} ms  max {max(durs):8.3f} ms")
    scopes = scope_seconds(t)
    total = sum(scopes.values())
    for scope, sec in sorted(scopes.items(), key=lambda kv: -kv[1]):
        print(f"scope {scope or '(unscoped)':12s} {sec:9.4f} s "
              f"{100 * sec / total:6.2f}% of device busy time")
    idle = idle_by_span(t) or {}
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        print(f"idle under {name or '(no span)':22s} {sec:9.4f} s "
              f"{100 * sec / span:6.2f}% of the slice")


if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    if len(sys.argv) < 2:
        print(__doc__)
        sys.exit(2)
    dump(*sys.argv[1:3])
