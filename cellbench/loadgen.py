"""The load generator: a child process of the run that sends real HTTP/SSE
requests over loopback on the schedule the seed gives, and writes one record
per request.  It never imports jax (asserted at the end, as chip_smoke.py
does): the parent holds the chip, and a second process that touched JAX would
fail or hang.

    python -m cellbench.loadgen --root R --plan plan.json --out records.jsonl

``plan.json`` holds the traffic parameters (cell overrides applied), the
seed, the window's length, the vocabulary size, the server's URL and the
per-request timeout.  The child prints ``ready`` when it can send, then waits
on stdin for ``go <t0>``: the moment, on the machine-wide monotonic clock,
at which load starts.  The measured window is [t0 + ramp_s, t0 + ramp_s +
seconds).  Times in the records are on that same clock.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import aiohttp

from cellbench import spec


def count_tokens(text: str) -> int:
    """Tokens in a streamed piece of text: the word-level tokenizer writes
    token i as the word ``w<i>``, so tokens are words."""
    return len(text.split())


def bad_words(text: str, vocab_size: int) -> int:
    """Words that are not a token of the vocabulary."""
    bad = 0
    for w in text.split():
        if w == "<unk>":
            continue
        if not (w[:1] == "w" and w[1:].isdigit() and int(w[1:]) < vocab_size):
            bad += 1
    return bad


async def send(session, plan: dict, req, due: float, records: list) -> None:
    """One streamed completion.  Everything that can go wrong lands in the
    record's ``status``; nothing raises."""
    body = {"model": plan["model"], "prompt": req.prompt,
            "max_tokens": req.max_tokens, "stream": True, "ignore_eos": True,
            **req.sampling}
    rec = {"index": req.index, "due": due, "sent": None, "first": None,
           "token_times": [], "end": None, "status": "error", "http": None,
           "n_tokens": 0, "max_tokens": req.max_tokens,
           "prompt_len": len(req.prompt), "finish_reason": None,
           "bad_tokens": 0, "error": None}
    records.append(rec)
    rec["sent"] = time.monotonic()
    try:
        async with asyncio.timeout(plan["request_timeout_s"]):
            async with session.post(plan["url"] + "/v1/completions",
                                    json=body) as r:
                rec["http"] = r.status
                if r.status != 200:
                    rec["error"] = (await r.text())[:300]
                    return
                done = False
                async for raw in r.content:
                    line = raw.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    data = line[5:].strip()
                    if data == "[DONE]":
                        done = True
                        break
                    now = time.monotonic()
                    for c in json.loads(data).get("choices", []):
                        n = count_tokens(c.get("text") or "")
                        if n:
                            rec["bad_tokens"] += bad_words(
                                c["text"], plan["vocab_size"])
                            rec["token_times"].extend([now] * n)
                        if c.get("finish_reason"):
                            rec["finish_reason"] = c["finish_reason"]
                rec["status"] = "ok" if done else "error"
                if not done:
                    rec["error"] = "stream ended without [DONE]"
    except TimeoutError:
        rec["status"] = "timeout"
    except asyncio.CancelledError:
        rec["status"] = "cancelled"
        raise
    except Exception as e:  # connection reset, bad JSON, ...
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec["end"] = time.monotonic()
        rec["n_tokens"] = len(rec["token_times"])
        if rec["token_times"]:
            rec["first"] = rec["token_times"][0]


async def sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        await asyncio.sleep(d)


async def open_loop(session, plan, sched, t0: float, records: list) -> None:
    tasks = []
    for k in range(sched.n):
        req = sched.request(k)
        due = t0 + req.due_s
        await sleep_until(due)
        tasks.append(asyncio.create_task(send(session, plan, req, due, records)))
    await asyncio.gather(*tasks)


async def closed_loop(session, plan, sched, t0: float, records: list) -> None:
    """``clients`` callers, started evenly over the first three quarters of
    the ramp so that they do not move in step; each sends its next request
    when the last one ended, and none starts one after the window closed."""
    t_end = t0 + sched.ramp_s + plan["seconds"]
    stagger = sched.ramp_s * 0.75
    counter = iter(range(10 ** 9))

    async def client(i: int) -> None:
        await sleep_until(t0 + stagger * i / max(1, sched.clients))
        while time.monotonic() < t_end:
            req = sched.request(next(counter))
            await send(session, plan, req, time.monotonic(), records)

    await asyncio.gather(*(client(i) for i in range(sched.clients)))


async def run(plan: dict, root: Path, out: Path) -> dict:
    gen = spec.load_module(root, "generators", plan["traffic"]["generator"])
    sched = gen.Schedule(plan["traffic"], plan["seed"], plan["seconds"],
                         plan["vocab_size"])
    records: list = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(connector=conn, timeout=timeout) as session:
        print("ready", flush=True)
        loop = asyncio.get_running_loop()
        line = await loop.run_in_executor(None, sys.stdin.readline)
        word, _, t0 = line.strip().partition(" ")
        if word != "go":
            raise SystemExit(f"expected 'go <t0>' on stdin, got {line!r}")
        t0 = float(t0)
        if sched.loop == "open":
            await open_loop(session, plan, sched, t0, records)
        else:
            await closed_loop(session, plan, sched, t0, records)
    with open(out, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return {"records": len(records), "t0": t0, "ramp_s": sched.ramp_s,
            "imported_jax": "jax" in sys.modules}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--root", required=True)
    p.add_argument("--plan", required=True)
    p.add_argument("--out", required=True)
    a = p.parse_args(argv)
    plan = spec.read_json(Path(a.plan))
    summary = asyncio.run(run(plan, Path(a.root), Path(a.out)))
    if summary["imported_jax"]:
        raise SystemExit("the load generator imported jax")
    print("done " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
