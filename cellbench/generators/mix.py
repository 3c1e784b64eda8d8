"""The general traffic generator: a traffic file's parameters and a seed in,
a schedule of requests out.  Pure standard library — the load generator's
process imports it and must never import jax.

Every seed offers the same work, so a difference between two runs is the
system's and not the dice's.  Sizes and arrival gaps are one draw, made from
the traffic file's own ``population_seed``; ``--seed`` draws the prompts'
token ids (and, in the harness, the weights).  In a closed loop the seed also
shuffles the order of the sizes.  In an open loop the draw is a fixed trace
one window long, played the same way in every run: the window holds every
request of the trace once, in the trace's order, and the ramp plays the end
of the trace before it.  (Chip runs of PR 24.  With sizes shuffled by the
seed, six seeds spread tokens/s by 4.8% and the 95th percentile of TTFT by
28%, while two runs of one seed agreed to 0.1% and 3%.  With the trace
rotated to a phase chosen by the seed, the same seed repeated its numbers
and another seed did not — tokens/s 225 or 233, each time — because the
first 20 s of a window are not yet in steady state and the phase chose which
requests fell there.  So the phase is fixed.)  What a cell on such a file
shows is therefore this one draw of lengths and arrivals; another draw is
another traffic file with another ``population_seed``.

Traffic file fields read here:

``loop``           ``"open"`` (requests sent on a schedule) or ``"closed"``
                   (``clients`` callers, each sending its next request when
                   the last one ended)
``rate_rps``       open loop: mean arrivals a second (Poisson)
``clients``        closed loop: number of callers
``prompt_len`` / ``output_len``
                   ``{"dist": "lognormal", "median", "sigma", "min", "max"}``,
                   ``{"dist": "uniform", "min", "max"}`` or
                   ``{"dist": "fixed", "value"}`` — in tokens
``sampling``       fields copied into each request body (temperature, top_p)
``ramp_s``         seconds of load before the measured window opens
``population_seed`` seed of the draw of sizes and gaps
"""

from __future__ import annotations

import math
import random
from dataclasses import dataclass


@dataclass
class Request:
    index: int
    due_s: float | None     # offset from load start; None in a closed loop
    prompt: list[int]       # token ids
    max_tokens: int
    sampling: dict


def _draw_len(rng: random.Random, d: dict) -> int:
    kind = d["dist"]
    if kind == "fixed":
        return int(d["value"])
    if kind == "uniform":
        return rng.randint(int(d["min"]), int(d["max"]))
    if kind == "lognormal":
        x = rng.lognormvariate(math.log(d["median"]), d["sigma"])
        return int(min(max(round(x), d["min"]), d["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


CLOSED_POPULATION = 256     # sizes a closed loop cycles through


def draw_sizes(traffic: dict, n: int) -> list[tuple[int, int]]:
    """The draw's n (prompt_len, max_tokens) pairs."""
    pop = random.Random(int(traffic["population_seed"]))
    return [(_draw_len(pop, traffic["prompt_len"]),
             _draw_len(pop, traffic["output_len"])) for _ in range(n)]


def cycle(traffic: dict, seconds: float) -> tuple[list[float], list[tuple[int, int]]]:
    """Open loop: the fixed trace one window long.  (gaps, sizes): gaps[j] is
    the time from request j to the next one (the last wraps to the first),
    scaled to sum to ``seconds`` so that the stated mean rate is exact."""
    n = max(1, round(float(traffic["rate_rps"]) * seconds))
    pop = random.Random(int(traffic["population_seed"]) + 1)
    gaps = [pop.expovariate(1.0) for _ in range(n)]     # Poisson arrivals
    scale = seconds / sum(gaps)
    return [g * scale for g in gaps], draw_sizes(traffic, n)


def prompt_ids(seed: int, index: int, n: int, vocab_size: int) -> list[int]:
    """Request ``index``'s prompt: distinct ids per request, so no two
    prompts share a prefix.  Ids 1..vocab-1: 0 is the tokenizer's unknown."""
    rng = random.Random(seed * 1_000_003 + index)
    return [rng.randrange(1, vocab_size) for _ in range(n)]


class Schedule:
    """What the load generator sends.  ``request(k)`` is the k-th request in
    the order played.  Open loop: ``n`` requests with due times (offsets from
    the start of load) in [0, ramp_s + seconds); the window holds the trace
    once, from its first request, and the ramp its end.  Closed loop: an
    endless sequence (``n`` is None); request k takes the k-th entry of the
    cycled population of sizes and a prompt of its own, so a repeat of a size
    is never a repeat of a prompt.
    ``sizes`` lists every (prompt, answer) length that can occur."""

    def __init__(self, traffic: dict, seed: int, seconds: float,
                 vocab_size: int):
        self.loop = traffic["loop"]
        self.ramp_s = float(traffic.get("ramp_s", 0.0))
        self.seed, self.vocab_size = seed, vocab_size
        self.sampling = dict(traffic.get("sampling", {}))
        if self.loop == "open":
            self.clients = 0
            gaps, self.sizes = cycle(traffic, seconds)
            n = len(gaps)
            played, t = [], self.ramp_s
            for j in range(n):                          # the window: the trace
                played.append((t, j))
                t += gaps[j]
            t, j = self.ramp_s, 0
            while True:                                 # the ramp: backwards
                j = (j - 1) % n
                t -= gaps[j]
                if t < 0:
                    break
                played.append((t, j))
            played.sort()
            self.dues = [due for due, _ in played]
            self._order = [j for _, j in played]
            self.n = len(played)
        elif self.loop == "closed":
            self.clients = int(traffic["clients"])
            self.dues, self.n = None, None
            self.sizes = draw_sizes(traffic, CLOSED_POPULATION)
            random.Random(seed).shuffle(self.sizes)
            self._order = None
        else:
            raise ValueError(f"unknown loop {self.loop!r}")

    def request(self, k: int) -> Request:
        j = self._order[k] if self._order is not None else k % len(self.sizes)
        p, o = self.sizes[j]
        return Request(
            index=k, due_s=self.dues[k] if self.dues is not None else None,
            prompt=prompt_ids(self.seed, k, p, self.vocab_size),
            max_tokens=o, sampling=self.sampling)
