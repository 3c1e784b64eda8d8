"""HTTP-level serving benchmark: concurrency sweep with TTFT/ITL/throughput.

Reference parity: examples/llm/benchmarks/perf.sh + README (genai-perf
concurrency sweep 1→256, ISL/OSL-controlled, ITL-matched throughput
comparison).  Drives a live OpenAI endpoint with synthetic prompts of a
fixed input length and measures, per concurrency level:

  * output tok/s (aggregate)
  * TTFT p50/p95 (ms)
  * ITL mean (ms/token)

Usage:
  python benchmarks/serve_bench.py --url http://127.0.0.1:8080 \
      --model llama --isl 3000 --osl 150 --concurrency 1,2,4,8,16

With --spawn-echo it boots an in-process HttpService around the echo engine
so the harness itself is testable without a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import aiohttp
from aiohttp import ClientSession


from benchmarks._common import percentile as _percentile
from benchmarks.scrape import (
    perf_model_stats_from_text,
    prefill_dispatch_stats_from_text,
)


async def one_request(session, url, model, prompt, osl):
    t0 = time.perf_counter()
    ttft = None
    n_tokens = 0
    async with session.post(
        f"{url}/v1/completions",
        json={"model": model, "prompt": prompt, "max_tokens": osl,
              "temperature": 0.0, "stream": True, "ignore_eos": True},
    ) as resp:
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {await resp.text()}")
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[6:]
            if data == "[DONE]":
                break
            chunk = json.loads(data)
            got = sum(1 for c in chunk.get("choices", []) if c.get("text"))
            if got and ttft is None:
                ttft = time.perf_counter() - t0
            n_tokens += got
    total = time.perf_counter() - t0
    return ttft or total, total, n_tokens


async def sweep_level(url, model, prompt, osl, concurrency, requests_per_conc):
    n_requests = concurrency * requests_per_conc
    sem = asyncio.Semaphore(concurrency)
    results = []

    async with ClientSession() as session:
        async def worker(i):
            async with sem:
                results.append(await one_request(session, url, model, prompt, osl))

        t0 = time.perf_counter()
        await asyncio.gather(*(worker(i) for i in range(n_requests)))
        wall = time.perf_counter() - t0

    ttfts = [r[0] * 1000 for r in results]
    itls = [
        (r[1] - r[0]) / max(r[2] - 1, 1) * 1000 for r in results if r[2] > 1
    ]
    total_tokens = sum(r[2] for r in results)
    return {
        "concurrency": concurrency,
        "requests": n_requests,
        "output_tok_s": round(total_tokens / wall, 1),
        "ttft_p50_ms": round(_percentile(ttfts, 50), 1),
        "ttft_p95_ms": round(_percentile(ttfts, 95), 1),
        "itl_mean_ms": round(statistics.fmean(itls), 2) if itls else 0.0,
    }


async def _fetch_metrics(url):
    """One GET of the endpoint's /metrics body, or None when the
    server doesn't expose it / is already gone (non-dynamo endpoint)."""
    try:
        async with ClientSession() as session:
            async with session.get(f"{url}/metrics") as resp:
                if resp.status != 200:
                    return None
                return await resp.text()
    except (OSError, aiohttp.ClientError):
        return None


async def prefill_dispatch_stats(url):
    """Scrape the serving endpoint's prefill-batching counters
    (dynamo_tpu_engine_prefill_* on /metrics): dispatch count and mean
    tokens-per-dispatch — the direct readout of the token-budget ragged
    prefill win.  Returns None when the server doesn't expose them
    (non-dynamo endpoint) or saw no prefill work.  Parsing lives in
    benchmarks/scrape.py on the registry names."""
    text = await _fetch_metrics(url)
    if text is None:
        return None
    return prefill_dispatch_stats_from_text(text)


async def perf_model_stats(url):
    """Scrape the dtperf predicted-vs-measured reconciliation gauges
    (dynamo_tpu_perf_* on /metrics): per-dispatch-kind roofline
    prediction, measured mean dispatch ms, and the model-error ratio
    (predicted/measured).  Returns None when the server doesn't expose
    them or no dispatch ran."""
    text = await _fetch_metrics(url)
    if text is None:
        return None
    return perf_model_stats_from_text(text)


def print_perf_table(rows, out=sys.stderr):
    """Predicted-vs-measured dispatch table (one row per jitted
    entrypoint kind) — the serve_bench readout of the dtperf loop."""
    print("# dtperf predicted vs measured dispatch (per kind):", file=out)
    print(f"# {'kind':<16} {'dispatches':>10} {'predicted_ms':>13} "
          f"{'measured_ms':>12} {'pred/meas':>10}", file=out)
    for kind in sorted(rows):
        r = rows[kind]
        def _f(key, fmt):
            return format(r[key], fmt) if key in r else "-"
        print(f"# {kind:<16} {int(r.get('dispatches_total', 0)):>10} "
              f"{_f('predicted_dispatch_ms', '>13.4f'):>13} "
              f"{_f('measured_dispatch_ms', '>12.4f'):>12} "
              # significant digits: on CPU the ratio sits orders of
              # magnitude below 1 and fixed decimals would print 0.0000
              f"{_f('model_error_ratio', '>10.3g'):>10}", file=out)


async def run(args):
    # Per-mode ISL calibration (ADVICE r5): the in-process modes
    # (--spawn-echo/--native) detokenize with WordLevel + WhitespaceSplit
    # — ONE token per "benchmark " repetition, so repetitions == tokens.
    # Plain --url mode talks to a real server whose BPE tokenizer splits
    # the same word into ~2 tokens; repeating it args.isl times would
    # DOUBLE the actual ISL vs the claimed one.  --tokens-per-word
    # overrides the mode default (1.0 in-process, 2.0 url) when the
    # target tokenizer is known to differ.
    tpw = args.tokens_per_word
    if tpw is None:
        tpw = 1.0 if getattr(args, "_in_process", False) else 2.0
    prompt = "benchmark " * max(1, round(args.isl / tpw))
    rows = []
    for conc in args.concurrency:
        row = await sweep_level(
            args.url, args.model, prompt, args.osl, conc, args.requests_per_conc
        )
        rows.append(row)
        print(json.dumps(row), flush=True)
    best = max(rows, key=lambda r: r["output_tok_s"])
    summary = {"metric": "serve_output_tok_s", "value": best["output_tok_s"],
               "unit": "tok/s", "best_concurrency": best["concurrency"]}
    prefill = await prefill_dispatch_stats(args.url)
    if prefill is not None:
        summary.update(prefill)
    perf = await perf_model_stats(args.url)
    if perf is not None:
        print_perf_table(perf)
        # bank the reconciliation alongside the measured numbers: one
        # error-ratio per kind plus the worst-case, so regressions in
        # the cost model itself show up in the banked history
        ratios = {k: r["model_error_ratio"] for k, r in perf.items()
                  if "model_error_ratio" in r}
        if ratios:
            summary["perf_model_error_ratio"] = ratios
    print(json.dumps(summary))
    return rows


async def _serve_and_sweep(args, engine, vocab, context_length):
    """Shared in-process bring-up for --spawn-echo and --native: WordLevel
    detok vocab → card → serving pipeline → HttpService, sweep against
    it, tear down."""
    import tempfile

    from tokenizers import Tokenizer
    from tokenizers import models as tok_models
    from tokenizers import pre_tokenizers

    from dynamo_tpu.llm.engines import build_serving_pipeline
    from dynamo_tpu.llm.http import HttpService, ModelManager
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    tok = Tokenizer(tok_models.WordLevel(vocab=vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    path = os.path.join(tempfile.mkdtemp(), "tok.json")
    tok.save(path)
    card = ModelDeploymentCard(name=args.model, tokenizer_path=path,
                               context_length=context_length)
    manager = ModelManager()
    manager.add_model(args.model, build_serving_pipeline(engine, card), card)
    svc = HttpService(manager, port=0)
    await svc.start()
    args.url = f"http://127.0.0.1:{svc.port}"
    try:
        return await run(args)
    finally:
        await svc.stop()


async def run_with_echo(args):
    """Self-contained mode for harness tests: echo engine behind HttpService."""
    from dynamo_tpu.llm.engines import EchoEngineCore

    return await _serve_and_sweep(
        args, EchoEngineCore(), {"<unk>": 0, "benchmark": 1}, 8192)


async def run_with_native(args):
    """On-chip mode (VERDICT r4 next #9): the REAL engine — random
    weights at the named geometry (profile_decode.MODELS), int8 on
    accelerators — behind HttpService, swept with the reference's
    genai-perf recipe (ISL/OSL, concurrency levels).  Prefix reuse is
    OFF so every identical synthetic prompt pays its full prefill, like
    distinct user prompts would."""
    import jax

    from benchmarks.profile_decode import MODELS
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()  # warm-start respawns (VERDICT r5 next #1)
    from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    on_accel = jax.default_backend() != "cpu"
    quant = on_accel
    cfg = ModelConfig(**MODELS[args.native],
                      dtype="bfloat16" if on_accel else "float32")
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), quantized=quant)
    jax.block_until_ready(params)
    batch = int(os.environ.get("DYNAMO_SERVE_BATCH",
                               "32" if on_accel else "4"))
    bs = 32 if on_accel else 16
    max_len = -(-(args.isl + args.osl + 64) // bs) * bs
    ecfg = EngineConfig(
        max_batch_size=batch, max_model_len=max_len, block_size=bs,
        num_blocks=batch * (max_len // bs) + 64,
        prefill_chunk_tokens=512 if on_accel else 0,
        # token-budget ragged prefill: pack concurrent prompts' chunks
        # into one dispatch (the sweep's higher concurrency levels are
        # exactly the backlog shape this converts from N round-trips to
        # ~ceil(tokens/budget))
        prefill_token_budget=int(os.environ.get(
            "DYNAMO_PREFILL_TOKEN_BUDGET", "1024" if on_accel else "0")),
        # unified mixed prefill+decode dispatch (one ragged step per
        # mixed turn); DYNAMO_UNIFIED_DISPATCH=1 to enable for a sweep
        unified_token_dispatch=bool(int(os.environ.get(
            "DYNAMO_UNIFIED_DISPATCH", "0"))),
        enable_prefix_reuse=False,
        cache_dtype="int8" if quant else None,
    )
    engine = AsyncLLMEngine(
        EngineCore(model, params, ecfg, eos_token_ids=[])).start()
    print(f"# native={args.native} quant={quant} batch={batch} "
          f"max_len={max_len}", file=sys.stderr)
    # full-coverage vocab: the random model emits arbitrary ids, and the
    # sweep counts tokens by non-empty SSE text — unknown ids decoding
    # to "" would score zero.  The prompt's words all map to <unk> (id
    # 0), which is fine: prefill cost depends on length, not content.
    vocab = {"<unk>": 0, **{f"w{i}": i for i in range(1, cfg.vocab_size)}}
    try:
        return await _serve_and_sweep(args, engine, vocab, max_len)
    finally:
        engine.shutdown()


def run_sim(args):
    """Virtual-time mode (--sim): sweep one traffic family over the
    load plane's offered-load levels instead of driving HTTP.  The
    macro-simulation runs the real router/admission/planner code
    against dtperf-modeled workers on a deterministic loop (see
    dynamo_tpu/load), so the rows come out in milliseconds of virtual
    time, seconds of wall clock, and are byte-reproducible per seed.
    Emits the same row/summary schema as the live sweep —
    ``concurrency`` carries the offered rps, rounded.

    ``--sim-router-shards N`` swaps the singleton KV router for the
    hash-partitioned sharded control plane (N scatter-gather index
    replicas) and scrapes its counters into the summary."""
    import dataclasses

    from dynamo_tpu.engine.counters import kv_shard_counters
    from dynamo_tpu.load.sim import LOAD_LEVELS, TOPOLOGIES, run_cell

    topo = TOPOLOGIES[args.sim_topology]
    shards = args.sim_router_shards
    if shards and shards != topo.router_shards:
        named = f"{args.sim_topology}r{shards}"
        topo = TOPOLOGIES.get(named) or dataclasses.replace(
            topo, name=named, router_shards=shards)
    kv_shard_counters.reset()
    rows = []
    for level in topo.levels or LOAD_LEVELS:
        res = run_cell(args.sim, topo, seed=args.sim_seed,
                       level=level, target_requests=args.sim_target)
        m = res["metrics"]
        row = {
            "concurrency": max(1, round(m["offered_rps"])),
            "requests": m["requests"],
            "output_tok_s": m["output_tok_s"],
            "ttft_p50_ms": m["ttft_p50_ms"],
            "ttft_p95_ms": m["ttft_p95_ms"],
            "itl_mean_ms": m["itl_mean_ms"],
            "level": level,
            "shed_rate": m["shed_rate"],
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    best = max(rows, key=lambda r: r["output_tok_s"])
    summary = {"metric": "serve_output_tok_s",
               "value": best["output_tok_s"], "unit": "tok/s",
               "best_concurrency": best["concurrency"],
               "sim_family": args.sim,
               "sim_topology": topo.name,
               "sim_seed": args.sim_seed}
    if topo.router_shards > 1:
        sc = kv_shard_counters
        summary["sim_router_shards"] = topo.router_shards
        summary["shard_scatters_total"] = sc.scatters_total
        summary["shard_gather_partial_total"] = sc.gather_partial_total
        summary["shard_gather_partial_frac"] = round(
            sc.gather_partial_frac, 4)
    print(json.dumps(summary))
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--url", default="http://127.0.0.1:8080")
    p.add_argument("--model", default="model")
    p.add_argument("--isl", type=int, default=3000)
    p.add_argument("--osl", type=int, default=150)
    p.add_argument("--concurrency", type=lambda s: [int(x) for x in s.split(",")],
                   default=[1, 2, 4, 8, 16])
    p.add_argument("--requests-per-conc", type=int, default=4)
    p.add_argument("--tokens-per-word", type=float, default=None,
                   help="tokens the target tokenizer produces per "
                        "'benchmark ' repetition (default: 1.0 for "
                        "--spawn-echo/--native WordLevel, 2.0 for --url "
                        "BPE servers) — keeps claimed ISL honest")
    p.add_argument("--spawn-echo", action="store_true",
                   help="boot an in-process echo-engine server (harness test)")
    p.add_argument("--native", default=None, metavar="MODEL",
                   help="boot the real engine at this geometry "
                        "(tiny|1b|8b|moe) behind an in-process server")
    p.add_argument("--sim", default=None, metavar="FAMILY",
                   help="macro-simulate this traffic family "
                        "(steady|agentic|burst|failure) on the load "
                        "plane's virtual clock instead of driving HTTP")
    p.add_argument("--sim-topology", default="w4",
                   help="with --sim: topology cell (w1|w4|w16)")
    p.add_argument("--sim-seed", type=int, default=0,
                   help="with --sim: deterministic-schedule seed")
    p.add_argument("--sim-target", type=int, default=None,
                   help="with --sim: requests at level 1.0 "
                        "(default: the load plane's pinned target)")
    p.add_argument("--sim-router-shards", type=int, default=None,
                   help="with --sim: partition the KV-router prefix "
                        "index across N scatter-gather shards "
                        "(default: the topology's own shard count)")
    args = p.parse_args(argv)
    args._in_process = bool(args.native or args.spawn_echo)
    if args.sim:
        # the simulation owns its own deterministic loop — run it
        # synchronously, never inside asyncio.run
        return run_sim(args)
    if args.native:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            # jax may already be imported: pin the platform through
            # jax.config as well as the env var
            from dynamo_tpu.utils import force_cpu_devices

            force_cpu_devices(1)
        coro = run_with_native(args)
    elif args.spawn_echo:
        coro = run_with_echo(args)
    else:
        coro = run(args)
    return asyncio.new_event_loop().run_until_complete(coro)


if __name__ == "__main__":
    sys.exit(0 if main() else 1)
