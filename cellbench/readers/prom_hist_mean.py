"""Mean of a histogram on the server's /metrics over the window: Δsum /
Δcount of ``<metric>_sum`` and ``<metric>_count`` (labels summed)."""


def read(ctx: dict, args: dict):
    before, after = ctx["edges"]
    s, c = f"prom.{args['metric']}_sum", f"prom.{args['metric']}_count"
    if s not in after or c not in after:
        return None
    dc = after[c] - before.get(c, 0.0)
    return args.get("scale", 1.0) * (after[s] - before.get(s, 0.0)) / dc if dc else None
