"""One run of one cell:

    python -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip(s) and is the server.  In order: refuse to run
without the chips the cell asks for; compile cache; model and weights from the
seed; the serving stack of ``dynamo-tpu run in=http out=tpu`` on a loopback
port; warm-up over HTTP of the shapes this cell's traffic uses; the check that
decides ``correct`` (cellbench/check.py); then the load generator (a child
process that never imports jax) drives the window, and the last line of the
standard output is the one JSON object of the contract.

``--rehearse`` lets the run go on without a TPU.  It is for the tests and the
CPU rehearsal; the driver never passes it, and a number from such a run is
never a device number.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # set-up counts from here

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

from cellbench import check, server, spec, stats, trace_reduce


def note(msg: str) -> None:
    print(f"# [{time.monotonic() - T_START:7.1f}s] {msg}", flush=True)


def require_devices(chips: int, rehearse: bool):
    """The devices this run uses, or exit: a measurement path that finds no
    chip fails, it does not fall back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise SystemExit(
            f"no TPU: jax.devices()[0] is {devs[0].platform} "
            f"({devs[0].device_kind}); the benchmark measures only on the chip")
    if len(devs) < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, jax sees {len(devs)}")
    return devs


def quiet_compile_logs() -> None:
    """The program turns jax's compile loggers up to DEBUG; a run's errors
    must stay readable in what comes back from the chip."""
    import logging

    for name in ("jax._src.compilation_cache", "jax._src.compiler"):
        logging.getLogger(name).setLevel(logging.WARNING)


class CompileCounter:
    """Programs built (compiled, or fetched from the persistent cache) while
    ``counting``: inside the window there should be none."""

    def __init__(self):
        import jax

        self.n, self.counting = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.counting and (event.endswith("backend_compile_duration")
                              or "cache_retrieval" in event):
            self.n += 1


def parse_prom(text: str) -> dict:
    """/metrics text -> {"prom.<name>": value}, labels summed."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{", 1)[0]
        try:
            out[f"prom.{name}"] = out.get(f"prom.{name}", 0.0) + float(value)
        except ValueError:
            pass
    return out


async def snapshot(served, session) -> dict:
    """The program's counters at one edge of the window, under dotted names."""
    from dynamo_tpu.obs.timeline import step_timeline

    core = served.core
    out = {f"core.{k}": v for k, v in core.metrics().items()
           if isinstance(v, (int, float))}
    if isinstance(getattr(core, "prompt_tokens_computed", None), int):
        # prefill work actually computed; metrics() does not carry it
        out["core.prompt_tokens_computed"] = core.prompt_tokens_computed
    tl = step_timeline.snapshot()
    out.update({f"timeline.{k}": v for k, v in tl.items()
                if isinstance(v, (int, float))})
    out.update({f"timeline.phases.{k}": v
                for k, v in tl.get("phases", {}).items()})
    out["timeline.host_gap_seconds_total"] = step_timeline.host_gap_s_total
    async with session.get(served.url + "/metrics") as r:
        out.update(parse_prom(await r.text()))
    return out


def warm_lengths(prompt_lens, ecfg) -> list[int]:
    """One prompt length for every prefill shape the traffic will use.  A
    prompt is prefilled in chunks; the last chunk is padded to a bucket and
    compiled per (bucket, number of chunks before it), and a prompt with k
    chunks before its last also runs every full chunk 0..k-1."""
    chunk = ecfg.prefill_chunk_tokens
    classes: dict[tuple[int, int], int] = {}
    for n in prompt_lens:
        before, last = divmod(n, chunk) if chunk else (0, n)
        if last == 0:
            before, last = before - 1, chunk
        classes.setdefault((before, ecfg.bucket_for(last)), n)
    return sorted(classes.values())


async def warm_up(served, sched, gen, seed: int, session) -> int:
    lens = warm_lengths([p for p, _ in sched.sizes], served.engine_config)
    for i, n in enumerate(lens):
        body = {"model": served.name, "max_tokens": 2, "ignore_eos": True,
                "prompt": gen.prompt_ids(seed, -(1 + i), n, served.vocab_size),
                **sched.sampling}
        async with session.post(served.url + "/v1/completions", json=body) as r:
            if r.status != 200:
                raise SystemExit(f"warm-up request failed: HTTP {r.status}: "
                                 f"{(await r.text())[:300]}")
            await r.read()
    return len(lens)


async def sampler(served, every_s: float, samples: list, stop: asyncio.Event):
    while not stop.is_set():
        m = served.core.metrics()
        samples.append({"t": time.monotonic(),
                        "active_slots": m["request_active_slots"],
                        "kv_usage": m["kv_usage_perc"],
                        "waiting": m["num_requests_waiting"]})
        try:
            await asyncio.wait_for(stop.wait(), every_s)
        except asyncio.TimeoutError:
            pass


async def trace_slice(settings: dict, w0: float, seconds: float, workdir: str):
    """Profile a slice in the middle of the window.  Returns the profile's
    directory and the slice's interval on the host clock."""
    import jax

    length = min(float(settings["trace"]["slice_s"]), seconds * 0.5)
    start = w0 + (seconds - length) / 2
    await asyncio.sleep(max(0.0, start - time.monotonic()))
    out = os.path.join(workdir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(settings["trace"]["python_tracer"])
    t0 = time.monotonic()
    await asyncio.to_thread(jax.profiler.start_trace, out, profiler_options=opts)
    await asyncio.sleep(length)
    t1 = time.monotonic()
    await asyncio.to_thread(jax.profiler.stop_trace)
    return out, (t0, t1)


async def load_phase(served, cell, seed: int, seconds: float, settings: dict,
                     root: Path, workdir: str, trace: bool, counter,
                     tag: str = "run") -> dict:
    """Start the load generator, open the window, read the program's counters
    at both edges, optionally profile a slice, wait for the drain."""
    import aiohttp

    plan = {"traffic": cell.traffic, "seed": seed, "seconds": seconds,
            "vocab_size": served.vocab_size, "url": served.url,
            "model": served.name,
            "request_timeout_s": settings["request_timeout_s"]}
    plan_path = os.path.join(workdir, f"{tag}-plan.json")
    records_path = os.path.join(workdir, f"{tag}-records.jsonl")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        [str(spec.REPO_ROOT), os.environ.get("PYTHONPATH", "")])}
    child = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "cellbench.loadgen", "--root", str(root),
        "--plan", plan_path, "--out", records_path,
        stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE, env=env)
    try:
        ready = (await child.stdout.readline()).decode().strip()
        if ready != "ready":
            raise SystemExit(f"load generator did not start: {ready!r}")
        ramp = float(cell.traffic.get("ramp_s", 0.0))
        t0 = time.monotonic() + 0.25
        child.stdin.write(f"go {t0!r}\n".encode())
        await child.stdin.drain()
        w0, w1 = t0 + ramp, t0 + ramp + seconds
        samples: list = []
        stop = asyncio.Event()
        async with aiohttp.ClientSession() as session:
            await asyncio.sleep(max(0.0, w0 - time.monotonic()))
            counter.counting = True
            before = await snapshot(served, session)
            sampling = asyncio.create_task(sampler(
                served, settings["sample_every_s"], samples, stop))
            tracing = (asyncio.create_task(trace_slice(settings, w0, seconds, workdir))
                       if trace else None)
            await asyncio.sleep(max(0.0, w1 - time.monotonic()))
            after = await snapshot(served, session)
            counter.counting = False
            stop.set()
            await sampling
            trace_dir, interval = (await tracing) if tracing else (None, None)
        done = (await child.stdout.readline()).decode().strip()
        rc = await child.wait()
        if rc != 0 or not done.startswith("done "):
            raise SystemExit(f"load generator failed (exit {rc}): {done!r}")
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
    with open(records_path) as f:
        records = [json.loads(line) for line in f]
    return {"records": records, "window": (w0, w1), "edges": (before, after),
            "samples": samples, "trace_dir": trace_dir,
            "trace_interval": interval, "loadgen": json.loads(done[5:])}


def device_report(devs, chips: int) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": max(peaks)}


def metric_args(desc: dict, config: dict) -> dict:
    """The arguments a metric's reader gets: the metric file's ``args``, and
    over them what the configuration's ``kernels`` block names for this
    metric (the operations to time, the cost module to load), so that one
    name stays one quantity whatever kernel does the work."""
    return {**desc.get("args", {}),
            **config.get("kernels", {}).get(desc["name"], {})}


def layer_metrics(root: Path, cell, ctx: dict) -> dict:
    out = {}
    on_chip = ctx["device"]["platform"] == "tpu"
    for m in spec.metrics_for(root, cell.name, "per_layer"):
        desc = spec.load_layer_metric(root, m["name"])
        reader = spec.load_module(root, "readers", desc["reader"])
        args = metric_args(desc, cell.config)
        value = reader.read(ctx, args)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif on_chip:
            # nothing to read: left out of the line, and this cell owes the
            # metric, so the log says what was missing
            why = (reader.missing(ctx, args) if hasattr(reader, "missing")
                   else "it found nothing to read")
            note(f"not reported: {m['name']} (reader {desc['reader']}): {why}")
    return out


async def run(a, root: Path, cell, settings: dict, devs, workdir: str) -> dict:
    import aiohttp

    counter = CompileCounter()
    split = {"devices_s": time.monotonic() - T_START}
    served = await server.start(cell.config, a.seed, workdir)
    split.update(served.split)
    note(f"serving on {served.url}: {split}")
    try:
        gen = spec.load_module(root, "generators", cell.traffic["generator"])
        sched = gen.Schedule(cell.traffic, a.seed, a.seconds, served.vocab_size)
        t = time.monotonic()
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as session:
            n_warm = await warm_up(served, sched, gen, a.seed, session)
        split["warm_up_s"] = time.monotonic() - t
        note(f"warmed {n_warm} prompt shapes")
        t = time.monotonic()
        verdict = await check.run(served, cell.config, settings, a.seed, root, gen)
        split["check_s"] = time.monotonic() - t
        note(f"check: {json.dumps(verdict)}")
        phase = await load_phase(served, cell, a.seed, a.seconds, settings,
                                 root, workdir, bool(a.trace), counter)
        w0, w1 = phase["window"]
        setup_s = w0 - T_START
        split["ramp_s"] = phase["loadgen"]["ramp_s"]
        e2e = stats.end_to_end(phase["records"], w0, w1, cell.chips)
        note(f"set-up split: {json.dumps(split)}")
        note(f"client: {json.dumps(e2e['values'])}")
        note(f"compiles_in_window: {counter.n}")
        short = sum(1 for r in stats.measured(phase["records"], w0, w1)
                    if r["status"] == "ok" and r["n_tokens"] < r["max_tokens"])
        note(f"requests: {len(phase['records'])} sent, {e2e['attempted']} "
             f"measured, {e2e['failed']} failed ({short} of them cut short); "
             f"malformed: {e2e['malformed'][:3]}")
        device = device_report(devs, cell.chips)
        result = {"correct": bool(verdict["ok"]) and not e2e["malformed"],
                  "attempted": e2e["attempted"], "failed": e2e["failed"]}
        if a.trace:
            trace = {}
            path = trace_reduce.find_xplane(phase["trace_dir"])
            if path:
                sel = settings["trace"]["device"][devs[0].platform]
                trace = trace_reduce.reduce(trace_reduce.load(
                    path, sel, settings["trace"]["host_event"]),
                    settings["trace"]["gap_depth"])
            if a.keep_trace and path:
                os.makedirs(a.keep_trace, exist_ok=True)
                shutil.copy(path, a.keep_trace)
            # a rehearsal off the chip has no peaks: no roofline share there
            peaks = (spec.load_peaks(root, devs[0].device_kind)
                     if devs[0].platform == "tpu" else None)
            ctx = {**phase, "root": root, "trace": trace, "device": device,
                   "config": cell.config, "chips": cell.chips, "peaks": peaks}
            result["metrics"] = layer_metrics(root, cell, ctx)
            device["busy_s"] = trace.get("busy_s", 0.0)
            device["window_s"] = trace.get("window_s", 0.0)
            if trace:
                result["breakdown"] = trace["breakdown"]
        else:
            values = {**e2e["values"], "setup_s": setup_s}
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in spec.metrics_for(root, cell.name, "end_to_end")
                if m["name"] in values}
        result["device"] = device
        return result
    finally:
        await served.stop()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tests and CPU rehearsals only: run without a TPU")
    p.add_argument("--root", default=str(spec.REPO_ROOT),
                   help="data root (BENCHMARK.json and cellbench/ data)")
    p.add_argument("--keep-trace", default=None,
                   help="copy the profile here (to read one by hand)")
    a = p.parse_args(argv)
    root = Path(a.root).resolve()
    cell = spec.load_cell(root, a.workload)
    settings = spec.load_settings(root)

    devs = require_devices(cell.chips, a.rehearse)
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    note(f"compile cache: {enable_persistent_cache()}")
    quiet_compile_logs()
    workdir = tempfile.mkdtemp(prefix="cellbench-")
    try:
        result = asyncio.run(run(a, root, cell, settings, devs, workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
