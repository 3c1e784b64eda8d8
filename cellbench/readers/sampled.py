"""Mean or maximum of a quantity the harness samples through the window
(``settings.json``, ``sample_every_s``) from ``EngineCore.metrics()``."""


def read(ctx: dict, args: dict):
    vals = [s[args["series"]] for s in ctx["samples"] if args["series"] in s]
    if not vals:
        return None
    v = max(vals) if args.get("stat", "mean") == "max" else sum(vals) / len(vals)
    return args.get("scale", 1.0) * v
