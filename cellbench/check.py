"""``correct``: decided before the window, by this one function, identically
under ``--trace 0`` and ``--trace 1``, with the profiler off.

What is compared.  A fixed set of prompts made from the seed (lengths in
``settings.json``; the longest crosses the prefill chunk), greedy, a few
tokens each, with the top-20 log-probabilities of every generated position,
sent over HTTP one at a time and then the same prompts together: two batch
compositions, both chosen here and not by arrival times.  For each answer the
float32 reference runs one forward over *the prompt plus the tokens the
server itself returned* (teacher forcing), and at every generated position the
server's log-probability of each of its top-20 tokens is set against the
reference's log-probability of the same token id.

What is not compared.  Sampled tokens, with anything: with random weights the
first and second logit lie within bf16 noise of each other, so an argmax flips
on rounding — and after a flip the server's context is still the one the
reference is given.  Nothing from inside the window, nothing the profiler
touches, no equality between runs, sets or seeds.

The rule is in the configuration's ``check`` block, set from the check alone
run on several seeds on the chip (PERF.md gives the margins):

``abs_tol``        |Δ log-probability| a compared pair may differ by
``share_within``   the share of pairs that must lie within ``abs_tol``; 1.0
                   for a dense model.  Below 1.0 only for a mixture of
                   experts, where a near-tie in the router can give a token
                   another eighth expert in bf16 than in float32: a few
                   positions then differ by more than rounding, and honestly
``median_tol``     bound on the median |Δ|: what a lower precision than the
                   configuration states (int8 K/V, int8 weights) moves first,
                   because it shifts every pair and not a few
"""

from __future__ import annotations

import asyncio
import statistics
from pathlib import Path

from cellbench import spec


def token_id(word: str) -> int:
    return 0 if word == "<unk>" else int(word[1:])


def check_prompts(settings: dict, seed: int, vocab_size: int, gen) -> list[list[int]]:
    """``gen`` is the cell's traffic generator: its ``prompt_ids`` makes a
    prompt of n distinct-per-request token ids from the seed."""
    return [gen.prompt_ids(seed, -(1000 + i), n, vocab_size)
            for i, n in enumerate(settings["check"]["prompt_lens"])]


async def ask(session, url: str, model: str, prompt: list[int], n: int) -> dict:
    """One greedy completion with top-20 log-probabilities.  Returns
    {"prompt", "tokens": [ids], "top": [{id: logprob}], "error"}."""
    body = {"model": model, "prompt": prompt, "max_tokens": n,
            "temperature": 0.0, "ignore_eos": True, "logprobs": 20}
    out = {"prompt": prompt, "tokens": [], "top": [], "error": None}
    try:
        async with session.post(url + "/v1/completions", json=body) as r:
            if r.status != 200:
                out["error"] = f"HTTP {r.status}: {(await r.text())[:300]}"
                return out
            choice = (await r.json())["choices"][0]
        lp = choice["logprobs"]
        out["tokens"] = [token_id(w) for w in lp["tokens"]]
        out["top"] = [{token_id(w): v for w, v in (pos or {}).items()}
                      for pos in lp["top_logprobs"]]
        if choice.get("finish_reason") != "length" or len(out["tokens"]) != n:
            out["error"] = (f"{len(out['tokens'])} tokens, finish_reason "
                            f"{choice.get('finish_reason')!r}; asked for {n}")
    except Exception as e:
        out["error"] = f"{type(e).__name__}: {e}"[:300]
    return out


async def collect(url: str, model: str, prompts: list[list[int]], n: int) -> list[dict]:
    """The two fixed batch compositions: alone, then together."""
    import aiohttp

    timeout = aiohttp.ClientTimeout(total=900)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        alone = [await ask(s, url, model, p, n) for p in prompts]
        together = await asyncio.gather(*(ask(s, url, model, p, n) for p in prompts))
    return alone + list(together)


def deltas(answer: dict, ref_logprobs) -> list[float]:
    """|server − reference| for every (generated position, top-20 token)."""
    return [abs(lp - float(ref_logprobs[pos][tid]))
            for pos, top in enumerate(answer["top"]) for tid, lp in top.items()]


def verdict(all_deltas: list[float], rule: dict) -> dict:
    if not all_deltas:
        return {"ok": False, "why": "nothing was compared"}
    within = sum(d <= rule["abs_tol"] for d in all_deltas) / len(all_deltas)
    med = statistics.median(all_deltas)
    ok = within >= rule["share_within"] and med <= rule["median_tol"]
    return {"ok": ok, "pairs": len(all_deltas), "max": max(all_deltas),
            "median": med, "share_within": within,
            "p99": sorted(all_deltas)[int(0.99 * (len(all_deltas) - 1))]}


def pad_len(n: int, multiple: int = 128) -> int:
    return -(-n // multiple) * multiple


def reference_logprobs(forward, params, answer: dict, n: int):
    """The reference's log-probabilities at the generated positions, teacher
    forced on the server's own tokens.  Sequences are padded to a multiple
    of 128 so that a handful of programs serves every seed."""
    import numpy as np

    seq = answer["prompt"] + answer["tokens"]
    tokens = np.zeros(pad_len(len(seq)), np.int32)
    tokens[: len(seq)] = seq
    # the position before generated token j holds its distribution
    at = np.arange(len(answer["prompt"]) - 1, len(seq) - 1, dtype=np.int32)
    return np.asarray(forward(params, tokens, at))


async def run(served, config: dict, settings: dict, seed: int, root: Path,
              gen) -> dict:
    """Ask, refer, compare.  Returns the verdict with its margins."""
    import jax

    n = int(settings["check"]["max_tokens"])
    prompts = check_prompts(settings, seed, served.vocab_size, gen)
    answers = await collect(served.url, served.name, prompts, n)
    errors = [a["error"] for a in answers if a["error"]]
    if errors:
        return {"ok": False, "why": f"check requests failed: {errors[:3]}"}
    ref = spec.load_module(root, "reference", config["reference"])
    forward = jax.jit(ref.make_forward(config))
    loop = asyncio.get_running_loop()
    all_deltas: list[float] = []
    for a in answers:
        # off the event loop: the server in this process keeps answering
        lps = await loop.run_in_executor(
            None, reference_logprobs, forward, served.core.params, a, n)
        all_deltas += deltas(a, lps)
    return verdict(all_deltas, config["check"])
