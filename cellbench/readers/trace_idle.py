"""Device idle share of the traced slice: 1 − union of device-operation
intervals / slice, mean over the devices used."""


def read(ctx: dict, args: dict):
    return ctx["trace"].get("idle_pct") if ctx.get("trace") else None
