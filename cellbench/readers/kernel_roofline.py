"""A kernel's share of its roofline in the traced slice.

Time: the self time of the device operations whose name matches ``pattern``
(mean over devices).  Work: the operations and bytes the algorithm needs for
the calls made in the slice, from ``costs/<cost>.py``.  Both are the metric
file's ``args`` unless the configuration's ``kernels`` block names its own for
this metric (``run.metric_args``): the name stays one quantity whatever
kernel does the work.

The calls are rebuilt from the client's records, because the trace does not
carry a call's dynamic lengths: a decode step that produced token j of a
request read a context of prompt + j, and a prompt was prefilled in the
chunks the engine's ``prefill_chunk_tokens`` gives, between its send and its
first token.  A token belongs to the slice when it arrived inside the slice's
interval on the host clock; the few calls cut by the slice's edges are the
error of this reading.  Least time = max(ops / peak FLOP/s, bytes / peak
bytes/s), per chip; share = least time / measured time.
"""

from cellbench import spec, trace_reduce


def reading(ctx: dict, args: dict) -> tuple[float | None, str]:
    """(share, "") or (None, what was missing)."""
    tr, interval = ctx.get("trace"), ctx.get("trace_interval")
    if not tr or not interval:
        return None, "no profile of a slice"
    if not ctx["peaks"]:
        return None, "no peaks for this device"
    seconds = trace_reduce.matching(tr["op_seconds"], args["pattern"])
    if not seconds:
        return None, f"no device operation matched {args['pattern']!r}"
    cost = spec.load_module(ctx["root"], "costs", args["cost"])
    calls = cost.calls(ctx["records"], interval, ctx["config"])
    if not calls:
        return None, f"costs/{args['cost']}.py found no call in the slice"
    ops, nbytes = cost.cost(ctx["config"], calls)
    peaks, chips = ctx["peaks"], ctx["chips"]
    least = max(ops / peaks["flops_per_s"][ctx["config"].get("dtype", "bfloat16")],
                nbytes / peaks["hbm_bytes_per_s"]) / chips
    return 100.0 * least / seconds, ""


def read(ctx: dict, args: dict):
    return reading(ctx, args)[0]


def missing(ctx: dict, args: dict) -> str:
    return reading(ctx, args)[1]
