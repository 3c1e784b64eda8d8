"""dynamo-tpu CLI.

Reference parity:
  * ``run``    — launch/dynamo-run (lib.rs:84, opt.rs:23,91):
                 ``run in=<http|text|stdin|batch:FILE|dyn://ep>
                 out=<echo|tpu|dyn://ep>`` builds the local pipeline
                 frontend → preprocessor → engine → detokenizer
                 (input/common.rs:78-96) or serves/consumes endpoints.
  * ``serve``  — deploy/dynamo/sdk `dynamo serve` (graph + YAML config,
                 process supervisor).
  * ``http``   — components/http standalone OpenAI frontend with dynamic
                 model discovery from the coordinator (discovery.rs:58).
  * ``models`` — launch/llmctl (add/list/remove ModelEntry records).

Invoke as ``python -m dynamo_tpu <cmd> ...``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import time
from pathlib import Path
from typing import Optional

log = logging.getLogger("dynamo_tpu.cli")

MODELS_PREFIX = "models/"  # under {namespace}/


# ------------------------------------------------------------ engine build ----


def _load_any_checkpoint(path: str, dtype):
    """(model, params, quantized) for any supported checkpoint format:
    native (dynamo-tpu quantize), GGUF, or HF safetensors dir (Llama
    family via the unified decoder; DeepSeek dirs via the MLA model; Jamba
    dirs via the hybrid recurrent model).
    ``dtype`` None = native checkpoints keep their stored dtype, others
    bf16."""
    from dynamo_tpu.models.checkpoint import is_native_checkpoint, load_checkpoint
    from dynamo_tpu.models.llama import LlamaModel

    if is_native_checkpoint(path):
        # pre-converted native checkpoint: params load in their serving
        # dtype — no per-start bf16 load + quantize pass
        cfg, params, quantized = load_checkpoint(path, dtype=dtype)
        return LlamaModel(cfg), params, quantized
    if path.endswith(".gguf"):
        from dynamo_tpu.llm.gguf import load_gguf_model

        cfg, params = load_gguf_model(path, dtype=dtype or "bfloat16")
        return LlamaModel(cfg), params, False
    from dynamo_tpu.models.loader import (
        is_deepseek_dir,
        is_jamba_dir,
        load_deepseek_dir,
        load_jamba_dir,
        load_model_dir,
    )

    if is_deepseek_dir(path):
        from dynamo_tpu.models.deepseek import DeepseekModel

        dcfg, params = load_deepseek_dir(path, dtype=dtype or "bfloat16")
        return DeepseekModel(dcfg), params, False
    if is_jamba_dir(path):
        from dynamo_tpu.models.hybrid_linear import HybridLinearModel

        jcfg, params = load_jamba_dir(path, dtype=dtype or "bfloat16")
        return HybridLinearModel(jcfg), params, False
    cfg, params = load_model_dir(path, dtype=dtype or "bfloat16")
    return LlamaModel(cfg), params, False


def _require_tpu() -> None:
    """out=tpu means the chip.  JAX falls back to the CPU when it finds no
    accelerator; a server that did so would answer, slowly, and nothing
    downstream could tell.  Only a caller that set JAX_PLATFORMS=cpu
    itself (tests, CPU rehearsals) gets the engine on the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    raise SystemExit(
        f"out=tpu found no TPU: jax.devices()[0] is {dev.platform} "
        f"({dev.device_kind}). Set JAX_PLATFORMS=cpu to run the engine on "
        "the CPU on purpose.")


def _log_startup(core, cache_dir) -> None:
    """ONE line saying what this server actually runs on: the device as
    JAX reports it, the attention implementation each phase will take
    and why, the native library, the compile cache, and the bytes each
    device holds after load.  chip_smoke.py reads it."""
    import jax

    from dynamo_tpu import native

    devs = jax.devices()
    stats = [d.memory_stats() for d in devs]
    log.info("startup %s", json.dumps({
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "attention": {
            phase: f"{impl} ({why})"
            for phase, (impl, why) in core.attention_impls().items()
        },
        "native": native.describe(),
        "compile_cache": cache_dir,
        "bytes_in_use": [s["bytes_in_use"] if s else None for s in stats],
    }))


def _build_local_engine(args) -> tuple[object, object]:
    """out=tpu|echo → (engine, card): the native JAX engine or the echo stub."""
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    if args.model_path is None:
        raise SystemExit(f"out={args.out} needs --model-path (weights + tokenizer)")
    from dynamo_tpu.llm.model_store import is_model_ref, resolve_model_sync

    if is_model_ref(args.model_path):
        # dyn://models/<name>: pull from the coordinator blob store into
        # the local cache (artifact distribution — only the pushing host
        # needs the checkpoint on disk).  Covers run, serve graphs, and
        # the colocated worker's two engines, since they all build here.
        import os as _os

        ref = args.model_path
        args.model_path = resolve_model_sync(
            ref,
            getattr(args, "coordinator", None)
            or _os.environ.get("DYNTPU_COORDINATOR"),
        )
        log.info("resolved %s -> %s", ref, args.model_path)
    is_gguf = args.model_path.endswith(".gguf")
    card = (
        ModelDeploymentCard.from_gguf(args.model_path, name=args.model_name)
        if is_gguf
        else ModelDeploymentCard.from_hf_dir(args.model_path, name=args.model_name)
    )

    if args.out == "echo":
        from dynamo_tpu.llm.engines import EchoEngineCore

        return EchoEngineCore(), card

    from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    # persistent XLA compilation cache: a restarted worker re-jits from
    # disk instead of recompiling
    cache_dir = enable_persistent_cache()

    # multi-host: join the jax.distributed mesh BEFORE any JAX array is
    # created — loading/quantizing weights initializes the backend, and
    # jax.distributed.initialize must run first for jax.devices() to be
    # global (runtime/multihost.py)
    from dynamo_tpu.runtime.multihost import MultiHostSpec, bootstrap
    from dynamo_tpu.utils.mesh import MESH_AXES, build_mesh

    nnodes = int(getattr(args, "nnodes", 1) or 1)
    if nnodes > 1:
        bootstrap(MultiHostSpec(
            num_processes=nnodes,
            process_id=int(getattr(args, "node_rank", 0) or 0),
            coordinator_url=getattr(args, "coordinator", None),
        ))
    _require_tpu()

    # --dtype default is None so the native branch can tell "explicitly
    # requested" from "use the checkpoint's stored dtype"
    dtype = getattr(args, "dtype", None)
    model, params, quantized = _load_any_checkpoint(args.model_path, dtype)
    if getattr(args, "quantize", "none") == "int8" and not quantized:
        if not hasattr(model, "quantize_params"):
            raise SystemExit(
                "--quantize int8 is not wired for this model family yet"
            )
        # int8 weight-only serving (models/quant.py): ~2x HBM headroom
        params = model.quantize_params(params)

    mesh = None
    tp = int(getattr(args, "tp", 1) or 1)
    dp = int(getattr(args, "dp", 1) or 1)
    if tp * dp > 1:
        mesh = build_mesh((dp, tp), MESH_AXES)

    cfg = EngineConfig(
        max_batch_size=args.max_batch_size,
        max_model_len=args.max_model_len,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        num_host_blocks=int(getattr(args, "num_host_blocks", 0) or 0),
        # persistent prefix-cache tier (llm/kv/persist.py): default off
        kv_persist_dir=(getattr(args, "kv_persist_dir", None) or None),
        kv_persist_max_bytes=int(
            getattr(args, "kv_persist_max_bytes", 0) or 0),
        kv_persist_ttl_s=float(getattr(args, "kv_persist_ttl", 0) or 0),
        cache_dtype=(
            "int8" if getattr(args, "kv_cache_dtype", "model") == "int8" else None
        ),
        spec_tokens=int(getattr(args, "spec_tokens", 0) or 0),
        draft_num_blocks=int(getattr(args, "spec_draft_num_blocks", 0) or 0),
        # ring-attention context parallelism for long prompts (needs a
        # mesh whose "data" axis is > 1)
        sp_prefill_threshold=int(
            getattr(args, "sp_prefill_threshold", 0) or 0),
        prefill_chunk_tokens=int(
            getattr(args, "prefill_chunk_tokens", 0) or 0),
        # token-budget ragged prefill: pack several waiting prompts'
        # chunks into one dispatch (docs/engine_scheduling.md)
        prefill_token_budget=int(
            getattr(args, "prefill_token_budget", 0) or 0),
        # unified mixed prefill+decode dispatch: one token-budget ragged
        # step per turn when both phases have work
        unified_token_dispatch=bool(
            getattr(args, "unified_token_dispatch", False)),
        # refused by EngineConfig when set
        lookahead_dispatch=bool(
            getattr(args, "lookahead_dispatch", False)),
        # where POST /debug/profile writes (profile_steps: inert, see
        # EngineConfig)
        profile_dir=(getattr(args, "profile_dir", None) or None),
        profile_steps=int(getattr(args, "profile_steps", 8) or 8),
    )
    draft = None
    dpath = getattr(args, "spec_draft_model", None)
    if dpath:
        if cfg.spec_tokens <= 0:
            raise SystemExit("--spec-draft-model requires --spec-tokens > 0")
        # draft-model speculation: a small same-tokenizer model proposes,
        # the target verifies (engine/draft.py).  Accepts the same
        # checkpoint formats as --model-path (native / GGUF / HF dir);
        # loads unsharded.
        dmodel, dparams, _ = _load_any_checkpoint(dpath, dtype)
        draft = (dmodel, dparams)
    core = EngineCore(
        model, params, cfg, mesh=mesh,
        eos_token_ids=card.eos_token_ids or None, draft=draft,
    )
    del params  # the engine holds the (sharded) copy; free the loader's
    _log_startup(core, cache_dir)
    return AsyncLLMEngine(core).start(), card


async def _build_out_engine(args, runtime=None):
    """Resolve out= to a ParsedRequest-level engine (full local pipeline or
    a remote endpoint client).  Returns (pipeline, card, raw_engine) — the
    raw engine is what worker-side publishers hook into (the pipeline
    wrapper hides .core)."""
    from dynamo_tpu.llm.engines import build_serving_pipeline

    if args.out.startswith("dyn://"):
        from dynamo_tpu.runtime.protocols import parse_endpoint_url

        ns, comp, ep = parse_endpoint_url(args.out)
        client = await runtime.namespace(ns).component(comp).endpoint(ep).client()
        return client, None, None
    engine, card = _build_local_engine(args)
    return build_serving_pipeline(engine, card), card, engine


def _runtime_config(args):
    from dynamo_tpu.runtime.config import RuntimeConfig

    kw = {}
    if args.coordinator:
        kw["coordinator_url"] = args.coordinator
    if args.namespace:
        kw["namespace"] = args.namespace
    return RuntimeConfig(**kw)


# ------------------------------------------------------------------- run ------


def _engine_failure(raw_engine):
    """The local engine's ``failed`` future (engine/async_engine.py): it
    resolves when a step could not build its program and the engine
    stopped itself.  None for echo and remote engines."""
    return getattr(raw_engine, "failed", None)


async def _serve_forever(raw_engine) -> None:
    """Block until ctrl-c — or until the local engine stops itself, which
    ends the process non-zero: a server that can never compile its step
    must not stay up answering every request with an error."""
    failed = _engine_failure(raw_engine)
    if failed is None:
        await asyncio.Event().wait()  # never set
        return
    raise SystemExit(
        f"engine stopped: {await asyncio.wrap_future(failed)!r}")


async def _cmd_run(args) -> None:
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime import serde

    serde.register_llm_types()
    needs_runtime = args.out.startswith("dyn://") or args.inp.startswith("dyn://")
    runtime = await DistributedRuntime.connect(_runtime_config(args)) if needs_runtime else None

    engine, card, raw_engine = await _build_out_engine(args, runtime)
    model_name = args.model_name or (card.name if card else "model")

    if args.inp.startswith("dyn://"):
        # serve the engine AT this endpoint (worker mode, Input::Endpoint)
        from dynamo_tpu.runtime.protocols import parse_endpoint_url

        ns, comp, ep = parse_endpoint_url(args.inp)
        await runtime.namespace(ns).component(comp).endpoint(ep).serve(engine)
        _attach_worker_publishers(runtime, raw_engine, ns)
        log.info("serving %s at %s — ctrl-c to stop", model_name, args.inp)
        await _serve_forever(raw_engine)

    elif args.inp == "http":
        from dynamo_tpu.llm.http.service import HttpService

        svc = HttpService(host=args.host, port=args.http_port,
                          profile_dir=getattr(args, "profile_dir", None))
        svc.manager.add_model(model_name, engine, card)
        await svc.start()
        log.info("OpenAI server on %s:%s — ctrl-c to stop", svc.host, svc.port)
        await _serve_forever(raw_engine)

    elif args.inp.startswith("text:"):
        await _one_prompt(engine, model_name, args.inp[5:], args)

    elif args.inp == "stdin":
        for line in sys.stdin:
            line = line.strip()
            if line:
                await _one_prompt(engine, model_name, line, args)

    elif args.inp.startswith("batch:"):
        await _batch(engine, model_name, Path(args.inp[6:]), args)

    else:
        raise SystemExit(f"unknown in={args.inp}")

    failed = _engine_failure(raw_engine)
    if failed is not None and failed.done():
        raise SystemExit(f"engine stopped: {failed.result()!r}")


async def _one_prompt(engine, model_name: str, prompt: str, args) -> None:
    from dynamo_tpu.llm.openai import parse_request
    from dynamo_tpu.runtime.engine import Context

    parsed = parse_request(
        {"model": model_name, "prompt": prompt, "max_tokens": args.max_tokens},
        chat=False,
    )
    async for out in engine.generate(Context(parsed)):
        if out.text:
            print(out.text, end="", flush=True)
    print()


async def _batch(engine, model_name: str, path: Path, args) -> None:
    """Input::Batch benchmark mode (ref input/batch.rs): JSONL in
    {"text": ...} → JSONL out with tokens + timing."""
    from dynamo_tpu.llm.openai import parse_request
    from dynamo_tpu.runtime.engine import Context

    async def one(text: str) -> dict:
        parsed = parse_request(
            {"model": model_name, "prompt": text, "max_tokens": args.max_tokens},
            chat=False,
        )
        t0 = time.perf_counter()
        ttft, n_tokens, chunks = None, 0, []
        async for out in engine.generate(Context(parsed)):
            if ttft is None:
                ttft = time.perf_counter() - t0
            n_tokens += len(out.token_ids)
            if out.text:
                chunks.append(out.text)
        dt = time.perf_counter() - t0
        return {
            "text": "".join(chunks),
            "output_tokens": n_tokens,
            "ttft_s": round(ttft or 0.0, 4),
            "total_s": round(dt, 4),
        }

    lines = [json.loads(l) for l in path.read_text().splitlines() if l.strip()]
    results = await asyncio.gather(*(one(l["text"]) for l in lines))
    out_path = path.with_suffix(".out.jsonl")
    with open(out_path, "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    total_tok = sum(r["output_tokens"] for r in results)
    total_s = max(r["total_s"] for r in results) if results else 0.0
    print(
        json.dumps(
            {
                "requests": len(results),
                "output_tokens": total_tok,
                "tok_per_s": round(total_tok / total_s, 2) if total_s else 0.0,
                "results": str(out_path),
            }
        )
    )


def _attach_worker_publishers(runtime, engine, namespace: str) -> None:
    """Real-engine worker: publish KV events + ForwardPassMetrics so the
    smart router and metrics component see this worker (publisher.rs
    parity).  No-op for engines without a core (echo, remote clients).
    Unwraps pipeline (``._engine``) and DecodeWorker (``.engine``)
    wrappers until an EngineCore surfaces."""
    core = None
    seen = set()
    while engine is not None and id(engine) not in seen:
        seen.add(id(engine))
        core = getattr(engine, "core", None)
        if core is not None:
            break
        engine = getattr(engine, "_engine", None) or getattr(engine, "engine", None)
    if core is None or not hasattr(core, "block_manager"):
        return
    from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher, KvMetricsPublisher

    wid = runtime.instance_id
    events = KvEventPublisher(runtime.coordinator, wid, namespace).start()
    core.block_manager.event_sink = events.sink
    metrics = KvMetricsPublisher(
        runtime.coordinator, wid, core.metrics, namespace
    ).start()
    # both publishers' flush loops must die with the runtime — nothing
    # else ever holds a reference that can reach their stop() (dtsan leak)
    runtime.on_shutdown(events.stop)
    runtime.on_shutdown(metrics.stop)
    # persistent tier replication: sync the content-addressed block store
    # with the coordinator index (boot-time pull = planner scale-up
    # pre-warm; periodic publish shares this worker's prefixes)
    store = getattr(core, "persist_store", None)
    if store is not None:
        from dynamo_tpu.llm.kv.persist import PersistReplicator

        replicator = PersistReplicator(runtime.coordinator, store, namespace)
        replicator.start_soon()
        runtime.on_shutdown(replicator.stop)


# ------------------------------------------------------------------ serve -----


async def _cmd_serve(args) -> None:
    from dynamo_tpu.sdk.config import ServiceConfig
    from dynamo_tpu.sdk.serving import ServeSupervisor

    graph = args.graph
    if getattr(args, "package", None):
        # packaged-graph deploy (the reference's bento flow): pull the
        # archive from the api-store, verify + unpack into the cache,
        # and serve its manifest entry with the package root importable
        # (sys.path for the supervisor's entry load, PYTHONPATH for the
        # worker processes it spawns)
        manifest, src_root = await _pull_package(
            args.package, args.api_store, args.package_cache)
        graph = graph if graph not in (None, "-") else manifest["entry"]
        sys.path.insert(0, str(src_root))
        prev = os.environ.get("PYTHONPATH")
        # no trailing separator when PYTHONPATH was unset: an empty
        # component means cwd, which packaged deploys must not import
        os.environ["PYTHONPATH"] = (
            f"{src_root}{os.pathsep}{prev}" if prev else str(src_root))
        log.info("serving package %s entry %s from %s",
                 args.package, graph, src_root)
    config = ServiceConfig.from_yaml(args.config) if args.config else ServiceConfig()
    sup = ServeSupervisor(graph, config, coordinator_url=args.coordinator)
    await sup.start()
    try:
        await sup.watch()
    finally:
        await sup.stop()


# ---------------------------------------------------------------- package -----


def _split_pkg_ref(ref: str) -> tuple[str, Optional[str]]:
    name, _, ver = ref.partition(":")
    return name, (ver or None)


async def _pull_package(ref: str, api_store: str, cache_root: str):
    """Resolve name[:version], reuse the local cache when it already
    holds that version, else download + unpack.  Returns (manifest,
    src_root)."""
    from aiohttp import ClientSession

    from dynamo_tpu.deploy.packaging import cache_lookup, cached_unpack

    name, ver = _split_pkg_ref(ref)
    async with ClientSession() as s:
        if ver is None:
            # cheap metadata GET resolves "latest" BEFORE any archive
            # transfer, so a cache hit skips the download entirely
            async with s.get(
                    f"{api_store}/api/v1/packages/{name}/latest") as resp:
                if resp.status == 404:
                    raise SystemExit(
                        f"package {ref!r} not found in {api_store}")
                resp.raise_for_status()
                ver = str((await resp.json())["version"])
        version = int(ver)
        hit = cache_lookup(cache_root, name, version)
        if hit is not None:
            return hit
        url = f"{api_store}/api/v1/packages/{name}/{version}/archive"
        async with s.get(url) as resp:
            if resp.status == 404:
                raise SystemExit(f"package {ref!r} not found in {api_store}")
            resp.raise_for_status()
            archive = await resp.read()
    return cached_unpack(archive, cache_root, name, version)


async def _cmd_package(args) -> None:
    from dynamo_tpu.deploy.packaging import build_package, read_manifest

    if args.pkg_cmd == "build":
        manifest = build_package(args.src, args.entry, args.name, args.out)
        print(json.dumps({"name": manifest["name"],
                          "entry": manifest["entry"],
                          "files": len(manifest["files"]),
                          "out": args.out}))
    elif args.pkg_cmd == "push":
        from aiohttp import ClientSession

        data = open(args.pkg, "rb").read()
        read_manifest(data)  # fail client-side with a good message
        async with ClientSession() as s:
            async with s.post(f"{args.api_store}/api/v1/packages",
                              data=data) as resp:
                body = await resp.text()
                if resp.status != 201:
                    raise SystemExit(f"push failed ({resp.status}): {body}")
                print(body)
    elif args.pkg_cmd == "pull":
        manifest, src_root = await _pull_package(
            args.ref, args.api_store, args.out)
        print(json.dumps({"name": manifest["name"],
                          "entry": manifest["entry"],
                          "src": str(src_root)}))
    elif args.pkg_cmd == "list":
        from aiohttp import ClientSession

        async with ClientSession() as s:
            async with s.get(f"{args.api_store}/api/v1/packages") as resp:
                resp.raise_for_status()
                print(json.dumps(await resp.json()))


# ------------------------------------------------------------------- http -----


async def _cmd_http(args) -> None:
    """Standalone OpenAI frontend: discovers ModelEntry records on the
    coordinator and builds a remote pipeline per model (ref
    components/http/src/main.rs + http/service/discovery.rs:58)."""
    from dynamo_tpu.llm.engines import build_serving_pipeline
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime import serde
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.protocols import parse_endpoint_url

    serde.register_llm_types()
    runtime = await DistributedRuntime.connect(_runtime_config(args))
    svc = HttpService(host=args.host, port=args.http_port)
    ns = args.namespace or "dynamo"
    clients: dict[str, object] = {}
    # discovery-event tasks, retained so a failed add_model (bad entry,
    # unreachable endpoint) is logged instead of vanishing with the task
    add_tasks: set[asyncio.Task] = set()

    def _add_done(task: asyncio.Task) -> None:
        add_tasks.discard(task)
        if not task.cancelled() and task.exception() is not None:
            log.error("add_model failed", exc_info=task.exception())

    async def add_model(name: str, entry: dict) -> None:
        e_ns, comp, ep = parse_endpoint_url(entry["endpoint"])
        client = await runtime.namespace(e_ns).component(comp).endpoint(ep).client()
        clients[name] = client
        card = (
            ModelDeploymentCard.from_hf_dir(entry["model_path"], name=name)
            if entry.get("model_path")
            else ModelDeploymentCard.from_dict(entry.get("card", {"name": name}))
        )
        svc.manager.add_model(name, build_serving_pipeline(client, card), card)
        log.info("model %s -> %s", name, entry["endpoint"])

    def on_event(event: str, key: str, value) -> None:
        name = key.rsplit("/", 1)[-1]
        if event == "put":
            task = asyncio.ensure_future(add_model(name, value))
            add_tasks.add(task)
            task.add_done_callback(_add_done)
        elif event == "delete":
            svc.manager.remove_model(name)
            clients.pop(name, None)

    _, snapshot = await runtime.coordinator.watch(f"{ns}/{MODELS_PREFIX}", on_event)
    for key, value in snapshot.items():
        try:
            await add_model(key.rsplit("/", 1)[-1], value)
        except Exception:
            # one bad registration must not take down the whole frontend
            log.exception("add_model %s failed at startup", key)

    await svc.start()
    log.info("OpenAI frontend on %s:%s (namespace %s)", svc.host, svc.port, ns)
    await asyncio.Event().wait()


# ------------------------------------------------------------- coordinator ----


async def _cmd_coordinator(args) -> None:
    """Run the control/event/queue-plane coordinator (etcd+NATS stand-in)."""
    from dynamo_tpu.runtime.transports.coordinator import CoordinatorServer

    server = await CoordinatorServer(
        host=args.host, port=args.port, data_dir=args.data_dir
    ).start()
    log.info("coordinator on %s (durable=%s)", server.url, bool(args.data_dir))
    await asyncio.Event().wait()


# ------------------------------------------------------------------ router ----


async def start_router_service(runtime, namespace: str = "default",
                               block_size: int = 16,
                               workers_endpoint: str | None = None):
    """Wire a live KvRouter behind `dyn://{ns}.router.generate` (shared by
    the CLI command and tests).  Returns the router.

    ``workers_endpoint`` ("component/endpoint", e.g. "backend/generate")
    watches that endpoint's discovery prefix so a dead worker's delete
    event evicts it from the router's candidate set immediately."""
    from dynamo_tpu.llm.kv_router.metrics_aggregator import KvRouterSubscriber
    from dynamo_tpu.llm.kv_router.router import KvRouter

    workers_prefix = None
    if workers_endpoint:
        comp, _, ep = workers_endpoint.partition("/")
        workers_prefix = f"{namespace}/components/{comp}/endpoints/{ep or 'generate'}/"
    router = KvRouter(block_size=block_size)
    sub = await KvRouterSubscriber(router, runtime.coordinator, namespace,
                                   workers_prefix=workers_prefix).start()
    # the subscriber's flush/watch tasks must die with the runtime, or
    # they outlive every caller that can reach sub.stop() (dtsan leak)
    runtime.on_shutdown(sub.stop)
    # KvRouter IS the endpoint engine: its generate() yields one
    # wire-serializable decision dict per request
    ep = runtime.namespace(namespace).component("router").endpoint("generate")
    await ep.serve(router)
    return router


async def _cmd_router(args) -> None:
    """Standalone KV-aware router service: serves routing decisions over
    `dyn://{ns}.router.generate` and keeps its prefix index + cost model
    live off the coordinator's KV-event/metrics subjects (ref
    components/router/src/main.rs)."""
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    runtime = await DistributedRuntime.connect(_runtime_config(args))
    ns = args.namespace or "default"
    await start_router_service(runtime, ns, args.block_size,
                               workers_endpoint=args.workers_endpoint)
    log.info("router service up: dyn://%s.router.generate", ns)
    await asyncio.Event().wait()


# ---------------------------------------------------------------- operator ----


async def _cmd_operator(args) -> None:
    """Run the reconcile loop over a watched directory of
    DynamoTpuDeployment specs and/or the DynamoTpuDeployment custom
    resources (--crd; ref deploy/dynamo/operator)."""
    from dynamo_tpu.deploy.operator import (
        KubectlCluster,
        KubectlCrSource,
        MemoryCluster,
        Operator,
    )

    if not args.specs_dir and not args.crd:
        raise SystemExit("operator needs a specs dir and/or --crd")
    cluster = MemoryCluster() if args.dry_run else KubectlCluster(
        context=args.context
    )
    coord = None
    if args.coordinator:
        from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient

        coord = await CoordinatorClient(args.coordinator, reconnect=True).connect()
    cr_source = (
        KubectlCrSource(context=args.context, read_only=args.dry_run)
        if args.crd else None
    )
    op = Operator(cluster, interval_s=args.interval, watch_dir=args.specs_dir,
                  coordinator=coord, cr_source=cr_source)
    if args.specs_dir:
        op.load_dir(args.specs_dir)
    log.info("operator watching %s (crd=%s, %d specs, dry_run=%s, "
             "coordinator=%s)", args.specs_dir, args.crd, len(op.specs),
             args.dry_run, args.coordinator)
    await op.run()


# ------------------------------------------------------------------ deploy ----


async def _cmd_deploy(args) -> None:
    """Render k8s manifests from a DynamoTpuDeployment spec (operator-lite,
    ref deploy/dynamo/operator CRD controller)."""
    from dynamo_tpu.deploy import DeploymentSpec
    from dynamo_tpu.deploy.renderer import render_manifests, render_to_dir

    spec = DeploymentSpec.from_yaml(Path(args.spec))
    if args.out:
        paths = render_to_dir(spec, args.out)
        for p in paths:
            print(p)
    else:
        import yaml as _yaml

        print(_yaml.safe_dump_all(render_manifests(spec), sort_keys=False))


# -------------------------------------------------------------- api store -----


async def _cmd_api_store(args) -> None:
    """Versioned graph registry with manifest rendering (api-store parity)."""
    from dynamo_tpu.components.api_store import ApiStore

    store = await ApiStore(db_path=args.db, host=args.host, port=args.port).start()
    log.info("api-store on http://%s:%s (db %s)", store.host, store.port, args.db)
    await asyncio.Event().wait()


# ---------------------------------------------------------------- metrics -----


async def _cmd_metrics(args) -> None:
    """Standalone metrics aggregation service (components/metrics parity):
    Prometheus /metrics fed by worker ForwardPassMetrics + kv_hit_rate."""
    from dynamo_tpu.components.metrics import MetricsService
    from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient

    coord = await CoordinatorClient(
        args.coordinator or "tcp://127.0.0.1:6180"
    ).connect()
    svc = await MetricsService(
        coord,
        namespace=args.namespace or "dynamo",
        host=args.host,
        port=args.port,
        push_url=args.push_url,
    ).start()
    log.info("metrics on http://%s:%s/metrics", svc.host, svc.port)
    await asyncio.Event().wait()


async def _cmd_planner(args) -> None:
    """SLA planner loop over the live metrics plane (reference Planner
    parity, docs/architecture.md:47): logs a per-tick plan — replica
    targets + role-flip decisions — from pool saturation and prefill
    queue depth.  Dry-run by default (LogActuator); in-cluster scaling
    actuates through the operator, local scaling through the sdk
    supervisor (docs/planner.md)."""
    from dynamo_tpu.llm.kv.persist import PrewarmActuator
    from dynamo_tpu.planner import LogActuator, PlannerConfig, PlannerLoop
    from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient

    coord = await CoordinatorClient(
        args.coordinator or "tcp://127.0.0.1:6180"
    ).connect()
    ns = args.namespace or "dynamo"
    loop = await PlannerLoop(
        coord,
        namespace=ns,
        config=PlannerConfig(
            queue_target_per_replica=args.target_per_replica,
            decode_target_usage=args.target_usage,
        ),
        prefill_component=args.prefill_component,
        decode_component=args.decode_component,
        interval_s=args.interval,
        # scale-ups also publish a persist pre-warm hint: fresh workers'
        # PersistReplicators pull the shared KV store at boot instead of
        # cold-starting (docs/kv_persistence.md)
        actuators=(LogActuator(), PrewarmActuator(coord, ns)),
    ).start()
    log.info("planner loop on namespace %r — ctrl-c to stop", loop.namespace)
    await asyncio.Event().wait()


async def _cmd_mock_worker(args) -> None:
    """GPU/TPU-free fake worker for exercising the router + metrics stack
    (components/metrics/src/bin/mock_worker.rs parity)."""
    from dynamo_tpu.components.mock_worker import MockWorker
    from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient

    coord = await CoordinatorClient(
        args.coordinator or "tcp://127.0.0.1:6180"
    ).connect()
    workers = [
        await MockWorker(
            coord, worker_id=args.worker_id + i, namespace=args.namespace or "dynamo"
        ).start()
        for i in range(args.count)
    ]
    log.info("%d mock worker(s) publishing — ctrl-c to stop", len(workers))
    await asyncio.Event().wait()


# ----------------------------------------------------------------- models -----


def _cmd_quantize(args) -> None:
    """Offline conversion: HF/GGUF -> native orbax checkpoint (+ tokenizer
    and config copied alongside so --model-path works unchanged)."""
    import shutil

    from dynamo_tpu.models.checkpoint import save_checkpoint
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.models.loader import load_model_dir

    t0 = time.monotonic()
    if args.src.endswith(".gguf"):
        from dynamo_tpu.llm.gguf import load_gguf_model

        cfg, params = load_gguf_model(args.src, dtype=args.dtype)
    else:
        cfg, params = load_model_dir(args.src, dtype=args.dtype)
    quantized = args.scheme == "int8"
    if quantized:
        params = LlamaModel(cfg).quantize_params(params)
    save_checkpoint(args.out, cfg, params, quantized=quantized)
    # tokenizer + config ride along so ModelDeploymentCard.from_hf_dir and
    # the preprocessor work off the converted dir directly
    src = Path(args.src)
    if src.is_dir():
        for name in ("tokenizer.json", "tokenizer_config.json", "config.json",
                     "generation_config.json", "special_tokens_map.json"):
            if (src / name).is_file():
                shutil.copy2(src / name, Path(args.out) / name)
    else:
        from dynamo_tpu.llm.model_card import ModelDeploymentCard

        card = ModelDeploymentCard.from_gguf(args.src)
        if card.tokenizer_path and Path(card.tokenizer_path).is_file():
            shutil.copy2(card.tokenizer_path, Path(args.out) / "tokenizer.json")
        else:
            log.warning(
                "gguf carried no materialisable tokenizer; place a "
                "tokenizer.json next to %s before serving", args.out,
            )
        if card.chat_template:
            # from_hf_dir picks this up, so chat rendering survives the
            # conversion instead of falling back to the default template
            (Path(args.out) / "chat_template.jinja").write_text(
                card.chat_template
            )
        # minimal config.json so from_hf_dir finds eos/context on the
        # converted dir (the gguf metadata carried them)
        (Path(args.out) / "config.json").write_text(json.dumps({
            "eos_token_id": card.eos_token_ids,
            "bos_token_id": card.bos_token_id,
            "max_position_embeddings": card.context_length,
        }))
    log.info("wrote %s (%s, scheme=%s) in %.1fs", args.out, cfg.dtype,
             args.scheme, time.monotonic() - t0)


async def _cmd_trace(args) -> None:
    """Fetch one request's Chrome trace-event JSON from a frontend's
    ``/debug/traces/{request_id}`` endpoint.  The output loads in
    chrome://tracing and https://ui.perfetto.dev; the serving processes
    must run with tracing on (``--trace`` or ``DYNAMO_TRACE=1``)."""
    from aiohttp import ClientSession

    url = f"{args.url.rstrip('/')}/debug/traces/{args.request_id}"
    async with ClientSession() as s:
        async with s.get(url) as resp:
            body = await resp.text()
            if resp.status != 200:
                raise SystemExit(f"trace fetch failed ({resp.status}): {body}")
    if args.out:
        Path(args.out).write_text(body)
        print(args.out)
    else:
        print(body)


async def _cmd_models(args) -> None:
    """llmctl parity: manage ModelEntry records on the coordinator — plus
    ``push``/``pull``: model-artifact distribution through the blob store
    (ref model.rs:150-199 NATS object store), so remote workers boot from
    a ``dyn://models/<name>`` ref with the checkpoint on one host only."""
    from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient

    ns = args.namespace or "dynamo"
    coord = await CoordinatorClient(
        args.coordinator or "tcp://127.0.0.1:6180"
    ).connect()
    try:
        if args.action == "push":
            from dynamo_tpu.llm.model_store import push_model

            if not args.name or not args.endpoint:
                raise SystemExit("usage: models push <name> <model-dir>")
            manifest = await push_model(coord, args.name, args.endpoint)
            total = sum(f["size"] for f in manifest["files"].values())
            print(f"pushed {args.name}: {len(manifest['files'])} files, "
                  f"{total} bytes, digest {manifest['digest'][:12]}")
        elif args.action == "pull":
            from dynamo_tpu.llm.model_store import pull_model

            if not args.name:
                raise SystemExit("usage: models pull <name> [--out DIR]")
            path = await pull_model(coord, args.name,
                                    cache_dir=getattr(args, "out", None))
            print(path)
        elif args.action == "add":
            entry = {"endpoint": args.endpoint, "model_path": args.model_path}
            await coord.kv_put(f"{ns}/{MODELS_PREFIX}{args.name}", entry)
            print(f"added {args.name} -> {args.endpoint}")
        elif args.action == "remove":
            ok = await coord.kv_delete(f"{ns}/{MODELS_PREFIX}{args.name}")
            print(f"removed {args.name}" if ok else f"no such model {args.name}")
        else:  # list
            items = await coord.kv_get_prefix(f"{ns}/{MODELS_PREFIX}")
            for key, value in sorted(items.items()):
                print(f"{key.rsplit('/', 1)[-1]}\t{value.get('endpoint')}")
    finally:
        await coord.close()


# ------------------------------------------------------------------ parser ----


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="dynamo-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--coordinator", default=None, help="tcp://host:port")
        sp.add_argument("--namespace", default=None)

    run = sub.add_parser("run", help="run a model or pipeline (dynamo-run parity)")
    run.add_argument("inout", nargs="+", help="in=<...> out=<...>")
    run.add_argument("--model-path", default=None)
    run.add_argument("--model-name", default=None)
    run.add_argument("--dtype", default=None,
                     help="activation dtype (default: bfloat16, or the "
                     "native checkpoint's stored dtype)")
    run.add_argument("--max-batch-size", type=int, default=8)
    run.add_argument("--spec-tokens", type=int, default=0,
                     help="speculative decoding: verify up to N proposed "
                     "tokens per dispatch (rejection-sampled — exact at "
                     "any temperature); proposals come from prompt-lookup "
                     "n-grams, or a draft model with --spec-draft-model")
    run.add_argument("--spec-draft-model", default=None,
                     help="small same-tokenizer model dir: draft-model "
                     "speculation instead of n-gram lookup")
    run.add_argument("--spec-draft-num-blocks", type=int, default=0,
                     help="draft cache block count (0 = same as "
                     "--num-blocks; shrink on HBM-tight deployments)")
    run.add_argument("--kv-cache-dtype", choices=["model", "int8"],
                     default="model",
                     help="model = cache in the model dtype; int8 = "
                     "quantized KV cache (ops/kv_quant.py): half the KV "
                     "HBM footprint and decode KV traffic")
    run.add_argument("--quantize", choices=["none", "int8"], default="none",
                     help="int8 weight-only quantization (halves weight HBM)")
    run.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    run.add_argument("--dp", type=int, default=1, help="data-parallel size")
    run.add_argument("--sp-prefill-threshold", type=int, default=0,
                     help="prompts at least this long prefill with the "
                     "sequence sharded over the mesh data axis (ring "
                     "attention context parallelism); 0 = off, needs dp>1")
    run.add_argument("--prefill-chunk-tokens", type=int, default=0,
                     help="chunked prefill: max prompt tokens per prefill "
                     "dispatch (0 = whole remainder); keeps decode ITL "
                     "flat under long prompts")
    run.add_argument("--prefill-token-budget", type=int, default=0,
                     help="token-budget ragged prefill: pack up to this "
                     "many tokens of several waiting prompts' chunks "
                     "into ONE dispatch (0 = one request per dispatch); "
                     "see docs/engine_scheduling.md")
    run.add_argument("--unified-token-dispatch", action="store_true",
                     help="unified mixed prefill+decode dispatch: when "
                     "both phases have work, run ONE token-budget "
                     "ragged step per turn (decode rows lead the flat "
                     "axis, prefill chunks pack the remaining "
                     "--prefill-token-budget, which defaults to 1024 "
                     "when unset); see docs/engine_scheduling.md")
    run.add_argument("--lookahead-dispatch", action="store_true",
                     help="refused: dispatch-ahead hides the host round "
                     "trip by default and has no option; see "
                     "docs/engine_scheduling.md")
    run.add_argument("--nnodes", type=int, default=1,
                     help="worker processes forming ONE mesh (multi-host)")
    run.add_argument("--node-rank", type=int, default=0)
    run.add_argument("--max-model-len", type=int, default=4096)
    run.add_argument("--block-size", type=int, default=16)
    run.add_argument("--num-blocks", type=int, default=512)
    run.add_argument("--num-host-blocks", type=int, default=0,
                     help="host-RAM KV offload tier (0 = disabled): "
                     "evicted device blocks park in host memory and "
                     "restore on prefix re-arrival")
    run.add_argument("--kv-persist-dir", default=None,
                     help="persistent prefix-cache tier (default off): "
                     "directory for the content-addressed KV block store "
                     "(llm/kv/persist.py).  Host-published blocks spill "
                     "here; restarts and coordinator-replicated peers "
                     "restore warm prefixes as cached_tokens.  Requires "
                     "--num-host-blocks > 0")
    run.add_argument("--kv-persist-max-bytes", type=int, default=0,
                     help="size cap for --kv-persist-dir (LRU by "
                     "last-touch; 0 = unbounded)")
    run.add_argument("--kv-persist-ttl", type=float, default=0,
                     help="TTL in seconds for persisted block groups "
                     "since last touch (0 = no expiry)")
    run.add_argument("--max-tokens", type=int, default=128)
    run.add_argument("--host", default="127.0.0.1")
    run.add_argument("--http-port", type=int, default=8080)
    run.add_argument("--trace", action="store_true",
                     help="enable the dtspan tracing plane (same as "
                     "DYNAMO_TRACE=1): per-request spans, exported as "
                     "Chrome trace JSON at /debug/traces/{request_id}")
    run.add_argument("--profile-dir", default=None,
                     help="directory for the jax.profiler captures an "
                     "operator asks for with POST /debug/profile?seconds=N "
                     "(in=http; refused when unset)")
    run.add_argument("--profile-steps", type=int, default=8,
                     help="inert (captures are asked for in seconds)")
    common(run)

    serve = sub.add_parser("serve", help="serve a graph of @service components")
    serve.add_argument("graph", nargs="?", default="-",
                       help="module.path:EntryService (optional with "
                            "--package: defaults to the manifest entry)")
    serve.add_argument("-f", "--config", default=None, help="YAML ServiceConfig")
    serve.add_argument("--package", default=None, metavar="NAME[:VER]",
                       help="serve a packaged graph pulled from the api-store")
    serve.add_argument("--api-store", default="http://127.0.0.1:7180",
                       dest="api_store")
    serve.add_argument("--package-cache",
                       default=os.path.expanduser("~/.cache/dynamo_tpu/packages"),
                       dest="package_cache")
    common(serve)

    pkg = sub.add_parser("package",
                         help="build/push/pull packaged serving graphs")
    pkg_sub = pkg.add_subparsers(dest="pkg_cmd", required=True)
    pb = pkg_sub.add_parser("build", help="archive a graph source tree")
    pb.add_argument("src", help="directory of graph sources")
    pb.add_argument("--entry", required=True,
                    help="module:Service relative to the package root")
    pb.add_argument("--name", required=True)
    pb.add_argument("-o", "--out", required=True, help="output .tar.gz")
    pp = pkg_sub.add_parser("push", help="upload a package to the api-store")
    pp.add_argument("pkg", help="package .tar.gz")
    pp.add_argument("--api-store", default="http://127.0.0.1:7180",
                    dest="api_store")
    pl = pkg_sub.add_parser("pull", help="download + unpack a package")
    pl.add_argument("ref", help="name[:version]")
    pl.add_argument("--api-store", default="http://127.0.0.1:7180",
                    dest="api_store")
    pl.add_argument("-o", "--out",
                    default=os.path.expanduser("~/.cache/dynamo_tpu/packages"))
    pls = pkg_sub.add_parser("list", help="list packages in the api-store")
    pls.add_argument("--api-store", default="http://127.0.0.1:7180",
                     dest="api_store")

    http = sub.add_parser("http", help="standalone OpenAI frontend w/ discovery")
    http.add_argument("--host", default="127.0.0.1")
    http.add_argument("--http-port", type=int, default=8080)
    common(http)

    coord = sub.add_parser("coordinator", help="run the coordinator service")
    coord.add_argument("--host", default="0.0.0.0")
    coord.add_argument("--port", type=int, default=6180)
    coord.add_argument("--data-dir", default=None,
                       help="WAL directory: KV + queues survive restarts")

    deploy = sub.add_parser("deploy", help="render k8s manifests from a deployment spec")
    deploy.add_argument("spec", help="DynamoTpuDeployment YAML")
    deploy.add_argument("-o", "--out", default=None, help="write one file per object")

    router = sub.add_parser(
        "router", help="standalone KV-aware router service"
    )
    router.add_argument("--block-size", type=int, default=16)
    router.add_argument("--workers-endpoint", default="backend/generate",
                        help="component/endpoint whose discovery deletes "
                             "evict workers from the router")
    common(router)

    operator = sub.add_parser(
        "operator", help="watch a specs dir and reconcile deployments"
    )
    operator.add_argument("specs_dir", nargs="?", default=None,
                          help="directory of DynamoTpuDeployment YAMLs")
    operator.add_argument("--crd", action="store_true",
                          help="watch DynamoTpuDeployment custom resources "
                               "(apply deploy/crd/ first) and write .status "
                               "back via the status subresource")
    operator.add_argument("--interval", type=float, default=5.0)
    operator.add_argument("--context", default=None, help="kubectl context")
    operator.add_argument("--dry-run", action="store_true",
                          help="reconcile against an in-memory cluster")
    operator.add_argument("--coordinator", default=None,
                          help="coordinator URL: enables truthful phases "
                               "from live registrations + queue-depth "
                               "autoscaling")

    store = sub.add_parser("api-store", help="versioned graph registry service")
    store.add_argument("--db", default="graphs.db")
    store.add_argument("--host", default="127.0.0.1")
    store.add_argument("--port", type=int, default=7180)

    metrics = sub.add_parser("metrics", help="metrics aggregation service (Prometheus)")
    metrics.add_argument("--host", default="127.0.0.1")
    metrics.add_argument("--port", type=int, default=9091)
    metrics.add_argument("--push-url", default=None, help="pushgateway URL (push mode)")
    common(metrics)

    planner = sub.add_parser(
        "planner", help="SLA planner loop (replica targets + role flips)")
    planner.add_argument("--interval", type=float, default=2.0)
    planner.add_argument("--prefill-component", default="prefill")
    planner.add_argument("--decode-component", default="decode")
    planner.add_argument("--target-per-replica", type=int, default=4,
                         help="prefill queue depth one replica absorbs")
    planner.add_argument("--target-usage", type=float, default=0.7,
                         help="decode saturation HPA target")
    common(planner)

    mock = sub.add_parser("mock-worker", help="fake worker publishing metrics/KV events")
    mock.add_argument("--worker-id", type=int, default=1)
    mock.add_argument("--count", type=int, default=1)
    common(mock)

    models = sub.add_parser(
        "models",
        help="manage model registrations (llmctl) + artifact push/pull",
    )
    models.add_argument(
        "action", choices=["add", "list", "remove", "push", "pull"]
    )
    models.add_argument("name", nargs="?")
    models.add_argument(
        "endpoint", nargs="?",
        help="dyn://ns.component.endpoint (add) | model dir (push)",
    )
    models.add_argument("--model-path", default=None)
    models.add_argument("--out", default=None,
                        help="pull: cache directory override")
    common(models)

    trace = sub.add_parser(
        "trace",
        help="fetch one request's Chrome trace-event JSON from a "
        "frontend's /debug/traces endpoint (server must run with "
        "--trace / DYNAMO_TRACE=1)",
    )
    trace.add_argument("request_id",
                       help="response id or the caller's x-request-id; "
                       "'engine' for the engine's steps")
    trace.add_argument("--url", default="http://127.0.0.1:8080",
                       help="frontend base URL")
    trace.add_argument("-o", "--out", default=None,
                       help="write the JSON here instead of stdout")

    from dynamo_tpu.analysis.cli import configure_parser as _lint_parser

    _lint_parser(sub.add_parser(
        "lint",
        help="async-safety + JAX/TPU static analysis "
        "(docs/static_analysis.md); exit 1 on non-baselined findings",
    ))

    quant = sub.add_parser(
        "quantize",
        help="convert an HF/GGUF checkpoint to a native serving checkpoint "
        "(int8 weight-only by default) — engines then start without the "
        "per-boot load+quantize pass",
    )
    quant.add_argument("src", help="HF model dir or .gguf file")
    quant.add_argument("out", help="output checkpoint dir")
    quant.add_argument("--scheme", choices=["int8", "none"], default="int8",
                       help="none = just convert/stack weights, no quant")
    quant.add_argument("--dtype", default="bfloat16")
    return p


def main(argv: Optional[list[str]] = None) -> None:
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    args = _parser().parse_args(argv)

    if args.cmd == "run":
        kv = dict(item.split("=", 1) for item in args.inout if "=" in item)
        if "in" not in kv or "out" not in kv:
            raise SystemExit("run needs in=<...> and out=<...>")
        args.inp, args.out = kv["in"], kv["out"]
        if getattr(args, "trace", False):
            from dynamo_tpu.obs import tracing

            tracing.enable(True)
        asyncio.run(_cmd_run(args))
    elif args.cmd == "serve":
        if args.graph == "-" and not args.package:
            raise SystemExit("serve needs a graph or --package")
        asyncio.run(_cmd_serve(args))
    elif args.cmd == "package":
        asyncio.run(_cmd_package(args))
    elif args.cmd == "http":
        asyncio.run(_cmd_http(args))
    elif args.cmd == "coordinator":
        asyncio.run(_cmd_coordinator(args))
    elif args.cmd == "deploy":
        asyncio.run(_cmd_deploy(args))
    elif args.cmd == "router":
        asyncio.run(_cmd_router(args))
    elif args.cmd == "operator":
        asyncio.run(_cmd_operator(args))
    elif args.cmd == "api-store":
        asyncio.run(_cmd_api_store(args))
    elif args.cmd == "metrics":
        asyncio.run(_cmd_metrics(args))
    elif args.cmd == "planner":
        asyncio.run(_cmd_planner(args))
    elif args.cmd == "mock-worker":
        asyncio.run(_cmd_mock_worker(args))
    elif args.cmd == "models":
        asyncio.run(_cmd_models(args))
    elif args.cmd == "trace":
        asyncio.run(_cmd_trace(args))
    elif args.cmd == "lint":
        from dynamo_tpu.analysis.cli import run_lint

        raise SystemExit(run_lint(args))
    elif args.cmd == "quantize":
        _cmd_quantize(args)


if __name__ == "__main__":
    main()
