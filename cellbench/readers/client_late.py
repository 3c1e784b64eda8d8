"""How late the load generator ran: a percentile of send time minus due
time over the measured requests.  A starved generator, not a fast server,
is what a large value means."""

from cellbench import stats


def read(ctx: dict, args: dict):
    late = [(r["sent"] - r["due"]) * 1e3
            for r in stats.measured(ctx["records"], *ctx["window"])
            if r["sent"] is not None]
    return stats.percentile(late, args.get("percentile", 95)) if late else None
