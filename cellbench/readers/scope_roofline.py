"""A named scope's share of its roofline in the traced slice.

Time: the self time of the device operations whose name-stack path holds the
``jax.named_scope`` ``args["scope"]`` at any depth (mean over devices), so
that the metric reads the same work whatever implements it: XLA fusions
today, a kernel tomorrow.  (``tracefile.scope_of`` keeps only the outermost
of a fixed list of scopes; this reader walks the profile's operation metadata
itself.)  Work: the operations and bytes from ``costs/<args["cost"]>.py`` for
the calls rebuilt from the client's records in the slice, as
readers/kernel_roofline.py does.  Least time = max(ops / peak FLOP/s, bytes /
peak bytes/s) per chip; share = least time / measured time.  None, and the
metric is left out, where the profile has no operation under the scope: a
program without it, or a backend whose profile carries no name stack.
"""

import re

from cellbench import spec, trace_reduce


def scope_seconds(ctx: dict, scope: str) -> tuple[float, float] | None:
    """(self seconds under ``scope``, busy seconds), each the mean over
    devices; None without a profile."""
    path = trace_reduce.find_xplane(ctx["trace_dir"]) if ctx.get("trace_dir") else None
    if not path:
        return None
    tf = spec.load_module(ctx["root"], "readers", "tracefile")
    settings = spec.load_settings(ctx["root"])
    platform = (ctx.get("device") or {}).get("platform", "tpu")
    sel = settings["trace"]["device"].get(platform)
    if not sel:
        return None
    plane_re, line_re = re.compile(sel["plane"]), re.compile(sel["line"])
    with open(path, "rb") as f:
        space = memoryview(f.read())
    under = busy = 0.0
    devices = 0
    for f, _, raw in tf._fields(space):
        if f != 1:
            continue
        plane = tf._plane(raw)
        if not plane_re.search(plane["name"]):
            continue
        names = plane["stat_names"]
        inside = {}
        for mid, (_, mstats) in plane["events"].items():
            tf_op = str(dict(tf._stat(s, names) for s in mstats).get("tf_op") or "")
            inside[mid] = scope in tf_op.split("/")
        rows = []
        for raw_line in plane["lines"]:
            lname, t0, events = tf._line(raw_line)
            if not line_re.search(lname):
                continue
            for ev in events:
                mid, off, dur, _ = tf._event(ev)
                if dur > 0:
                    rows.append(["in" if inside.get(mid) else "out",
                                 t0 + off / 1e3, dur / 1e3])
        if rows:
            by = trace_reduce.self_times(rows)
            under += by.get("in", 0.0)
            busy += sum(by.values())
            devices += 1
    if not devices:
        return None
    return under / devices, busy / devices


def reading(ctx: dict, args: dict) -> tuple[float | None, str]:
    if not ctx.get("trace_interval"):
        return None, "no profile of a slice"
    if not ctx["peaks"]:
        return None, "no peaks for this device"
    sec = scope_seconds(ctx, args["scope"])
    if not sec or not sec[0]:
        return None, f"no device operation under the scope {args['scope']!r}"
    cost = spec.load_module(ctx["root"], "costs", args["cost"])
    calls = cost.calls(ctx["records"], ctx["trace_interval"], ctx["config"])
    if not calls:
        return None, f"costs/{args['cost']}.py found no call in the slice"
    ops, nbytes = cost.cost(ctx["config"], calls)
    peaks = ctx["peaks"]
    least = max(ops / peaks["flops_per_s"][ctx["config"].get("dtype", "bfloat16")],
                nbytes / peaks["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * least / sec[0], ""


def read(ctx: dict, args: dict):
    return reading(ctx, args)[0]


def missing(ctx: dict, args: dict) -> str:
    return reading(ctx, args)[1]
