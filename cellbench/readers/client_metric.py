"""A statistic of the client's records (``stats.end_to_end``'s values) that
stands beside the end-to-end metrics in the traced run: a tail too wide to
carry a bound, or the steadier median beside a judged tail."""

from cellbench import stats


def read(ctx: dict, args: dict):
    out = stats.end_to_end(ctx["records"], *ctx["window"], ctx["chips"])
    return out["values"].get(args["metric"])
