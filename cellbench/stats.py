"""From the load generator's records to the end-to-end metrics.  Plain
arithmetic on plain dicts, so that a test can hand-make the records.

A request is *measured* when it was due inside the window [w0, w1): due on
the schedule in an open loop, sent in a closed one (there the two are the
same moment).  Measured requests that were not delivered — an error, a
refusal, a time-out, or a stream cut short of the tokens it asked for —
count in ``attempted`` and ``failed`` and nowhere else; they have no latency
and never touch ``correct``.  A short stream is among them because load
causes it: the engine ends a request at ``length`` early when the cache has
no block left for it (seen on the chip at a full cache, PR 24), and what
timing can cause must not decide ``correct``.
"""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def measured(records: list[dict], w0: float, w1: float) -> list[dict]:
    return [r for r in records if w0 <= r["due"] < w1]


def delivered(records: list[dict]) -> list[dict]:
    """Streams that ended and carried at least the tokens asked for."""
    return [r for r in records
            if r["status"] == "ok" and r["n_tokens"] >= r["max_tokens"]]


def ttft_ms(rec: dict) -> float:
    return (rec["first"] - rec["due"]) * 1e3


def itl_ms(rec: dict) -> float | None:
    """A request's mean gap between tokens: (last − first) / (tokens − 1).
    Per request and not per gap, because tokens can arrive in bursts."""
    t = rec["token_times"]
    if len(t) < 2:
        return None
    return (t[-1] - t[0]) / (len(t) - 1) * 1e3


def tokens_in_window(records: list[dict], w0: float, w1: float) -> int:
    """Output tokens that arrived inside the window, whatever request they
    belong to: a rate is taken over all the work of the window."""
    return sum(1 for r in records for t in r["token_times"] if w0 <= t < w1)


def malformed(rec: dict) -> str | None:
    """What is wrong with a *delivered* stream, or None.  Independent of
    timing and load: more tokens than asked for, a reason to stop other than
    the length asked for, a token outside the vocabulary."""
    if rec["n_tokens"] > rec["max_tokens"]:
        return f"{rec['n_tokens']} tokens, asked for {rec['max_tokens']}"
    if rec["finish_reason"] != "length":
        return f"finish_reason {rec['finish_reason']!r}"
    if rec["bad_tokens"]:
        return f"{rec['bad_tokens']} tokens outside the vocabulary"
    return None


def end_to_end(records: list[dict], w0: float, w1: float, chips: int) -> dict:
    """{"attempted", "failed", "malformed": [...], "values": {name: value}}."""
    ms = measured(records, w0, w1)
    ok = delivered(ms)
    bad = [f"request {r['index']}: {m}" for r in ok if (m := malformed(r))]
    values: dict[str, float] = {}
    ttfts = [ttft_ms(r) for r in ok if r["first"] is not None]
    itls = [x for r in ok if (x := itl_ms(r)) is not None]
    if ttfts:
        values["ttft_mean_ms"] = sum(ttfts) / len(ttfts)
        values["ttft_p50_ms"] = percentile(ttfts, 50)
        values["ttft_p95_ms"] = percentile(ttfts, 95)
    if itls:
        values["itl_p50_ms"] = percentile(itls, 50)
        values["itl_p95_ms"] = percentile(itls, 95)
    values["tok_s_chip"] = tokens_in_window(records, w0, w1) / (w1 - w0) / chips
    return {"attempted": len(ms), "failed": len(ms) - len(ok),
            "malformed": bad, "values": values}
