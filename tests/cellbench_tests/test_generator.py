"""The schedule is a pure function of seed and traffic file; every seed
offers the same sizes and gaps; the load generator's
process never imports jax."""

import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

from cellbench import spec

REPO = Path(__file__).resolve().parents[2]
OPEN = {**spec.read_json(REPO / "cellbench/traffic/chat-open.json"),
        "rate_rps": 2.4}
CLOSED = spec.read_json(REPO / "cellbench/traffic/decode-closed.json")
mix = spec.load_module(REPO, "generators", "mix")


def dump(s, n):
    return [(r.due_s, r.prompt, r.max_tokens) for r in map(s.request, range(n))]


@pytest.mark.parametrize("traffic", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_schedule(traffic):
    a = mix.Schedule(traffic, 2**31 + 7, 20, 32768)
    b = mix.Schedule(traffic, 2**31 + 7, 20, 32768)
    assert dump(a, 40) == dump(b, 40)


def test_closed_loop_seeds_shuffle_one_population():
    a = mix.Schedule(CLOSED, 1, 20, 32768)
    b = mix.Schedule(CLOSED, 2, 20, 32768)
    assert a.sizes != b.sizes and sorted(a.sizes) == sorted(b.sizes)
    assert a.request(0).prompt != b.request(0).prompt


def in_window(s, seconds):
    """(size, gap to the next request) of the requests due inside the window."""
    ks = [k for k in range(s.n) if s.ramp_s <= s.dues[k] < s.ramp_s + seconds]
    reqs = [s.request(k) for k in ks]
    ends = [r.due_s for r in reqs[1:]] + [s.ramp_s + seconds]
    return [((len(r.prompt), r.max_tokens), round(e - r.due_s, 9))
            for r, e in zip(reqs, ends)]


def test_open_loop_plays_one_trace_whatever_the_seed():
    traffic = {**OPEN, "rate_rps": 2.4}
    a, b = (mix.Schedule(traffic, seed, 45, 32768) for seed in (2**31 + 5, 77))
    wa, wb = in_window(a, 45), in_window(b, 45)
    assert len(wa) == round(2.4 * 45) and wa == wb     # same work, same order
    assert a.dues == b.dues == sorted(a.dues) and a.dues[0] >= 0.0
    assert a.dues[-1] < a.ramp_s + 45
    # the ramp plays the end of the trace, so the window opens under load
    n_ramp = sum(d < a.ramp_s for d in a.dues)
    assert abs(n_ramp - 2.4 * a.ramp_s) <= 12
    assert [a._order[k] for k in range(n_ramp)] == list(range(a.n - 2 * n_ramp, a.n - n_ramp))
    assert a.request(0).prompt != b.request(0).prompt   # the seed draws the ids
    # another draw is another traffic file
    c = mix.Schedule({**traffic, "population_seed": 1}, 77, 45, 32768)
    assert in_window(c, 45) != wb


def test_lengths_stay_inside_their_limits():
    s = mix.Schedule(OPEN, 5, 45, 32768)
    p, o = OPEN["prompt_len"], OPEN["output_len"]
    assert all(p["min"] <= a <= p["max"] and o["min"] <= b <= o["max"]
               for a, b in s.sizes)
    r = s.request(0)
    assert all(1 <= t < 32768 for t in r.prompt)
    assert (len(r.prompt), r.max_tokens) in s.sizes


def test_closed_loop_never_repeats_a_prompt():
    s, n = mix.Schedule(CLOSED, 9, 10, 32768), mix.CLOSED_POPULATION
    assert s.request(0).max_tokens == s.request(n).max_tokens == 256
    assert len(s.request(0).prompt) == len(s.request(n).prompt)
    assert s.request(0).prompt != s.request(n).prompt


def test_loadgen_process_never_imports_jax():
    code = ("import sys, cellbench.loadgen, cellbench.spec;"
            "from pathlib import Path;"
            f"cellbench.spec.load_module(Path({str(REPO)!r}), 'generators', 'mix');"
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO)
