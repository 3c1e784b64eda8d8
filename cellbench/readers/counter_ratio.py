"""Δnumerator / Δdenominator of two of the program's counters between the
edges of the window.  Names are dotted paths into the snapshot the harness
takes at each edge: ``core.<key of EngineCore.metrics() or counter
attribute>``, ``timeline.<key of step_timeline.snapshot()>``."""


def read(ctx: dict, args: dict):
    before, after = ctx["edges"]
    try:
        num = after[args["num"]] - before[args["num"]]
        den = after[args["den"]] - before[args["den"]]
    except KeyError:
        return None
    return args.get("scale", 1.0) * num / den if den else None
