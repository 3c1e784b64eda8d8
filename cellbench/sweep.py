"""Tools of the benchmark that the driver never calls; both pay the set-up
once and then repeat one phase in the same process.

The knee of an open-loop cell — the highest offered rate at which completions
keep up with arrivals — is found once, when the cell is defined:

    python -m cellbench.sweep --workload W --seed 1 --rates 3,4,5,6,7 --seconds 30

prints one JSON row per rate: arrivals, completions, requests outstanding at
both edges of the step, the end-to-end metrics.  The cell's file under
``cells/`` then fixes the rate at four fifths of the highest rung that held
(the knee lies between that rung and the next).

The check that decides ``correct`` needs no window, so its margins over
several seeds cost one set-up (the weights are made anew for each seed):

    python -m cellbench.sweep --workload W --check-seeds 11,12,13,14,15

``--serve kv_cache_dtype=int8`` changes a ``run`` flag of the configuration
for that call: the negative control of the check, which must then fail.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

from cellbench import check, run as runner, server, spec, stats


def outstanding(records: list[dict], t: float) -> int:
    return sum(1 for r in records
               if r["due"] <= t and (r["end"] is None or r["end"] > t))


async def knee_sweep(a, root: Path, cell, settings: dict, workdir: str) -> None:
    import aiohttp

    counter = runner.CompileCounter()
    served = await server.start(cell.config, a.seed, workdir)
    try:
        gen = spec.load_module(root, "generators", cell.traffic["generator"])
        top = {**cell.traffic, "rate_rps": max(a.rates)}
        sched = gen.Schedule(top, a.seed, a.seconds, served.vocab_size)
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as session:
            await runner.warm_up(served, sched, gen, a.seed, session)
        for i, rate in enumerate(a.rates):
            cell.traffic = {**cell.traffic, "rate_rps": rate}
            ph = await runner.load_phase(
                served, cell, a.seed + i, a.seconds, settings, root, workdir,
                False, counter, tag=f"rate{i}")
            w0, w1 = ph["window"]
            recs = ph["records"]
            e2e = stats.end_to_end(recs, w0, w1, cell.chips)
            print(json.dumps({
                "rate_rps": rate, "seconds": a.seconds,
                "arrivals": e2e["attempted"], "failed": e2e["failed"],
                "completions": sum(1 for r in recs if r["status"] == "ok"
                                   and w0 <= r["end"] < w1),
                "outstanding_start": outstanding(recs, w0),
                "outstanding_end": outstanding(recs, w1),
                "waiting_max": max((s["waiting"] for s in ph["samples"]), default=0),
                "active_slots_mean": (sum(s["active_slots"] for s in ph["samples"])
                                      / max(1, len(ph["samples"]))),
                "compiles_in_window": counter.n, **e2e["values"]}), flush=True)
    finally:
        await served.stop()


async def check_seeds(a, root: Path, cell, settings: dict, workdir: str) -> None:
    import jax

    served = await server.start(cell.config, a.check_seeds[0], workdir)
    gen = spec.load_module(root, "generators", cell.traffic["generator"])
    try:
        for i, seed in enumerate(a.check_seeds):
            if i:   # new weights in the engine's own shardings, old ones freed
                served.core.params = None
                served.core.params = server.make_params(
                    served.model, seed, served.core.mesh)
                jax.block_until_ready(served.core.params)
            t = time.monotonic()
            v = await check.run(served, cell.config, settings, seed, root, gen)
            print(json.dumps({"seed": seed, "seconds": time.monotonic() - t,
                              **v}), flush=True)
    finally:
        await served.stop()


def main(argv=None) -> int:
    ints = lambda s: [int(x) for x in s.split(",")]
    floats = lambda s: [float(x) for x in s.split(",")]
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rates", type=floats, default=None)
    p.add_argument("--check-seeds", type=ints, default=None)
    p.add_argument("--serve", action="append", default=[], metavar="FLAG=VALUE",
                   help="override a flag of the configuration's serve block")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--root", default=str(spec.REPO_ROOT))
    a = p.parse_args(argv)
    root = Path(a.root).resolve()
    cell = spec.load_cell(root, a.workload)
    cell.config["serve"].update(kv.split("=", 1) for kv in a.serve)
    settings = spec.load_settings(root)
    runner.require_devices(cell.chips, a.rehearse)
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    runner.quiet_compile_logs()
    workdir = tempfile.mkdtemp(prefix="cellbench-")
    try:
        job = check_seeds if a.check_seeds else knee_sweep
        asyncio.run(job(a, root, cell, settings, workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
