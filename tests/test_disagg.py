"""Disaggregated prefill/decode tests.

Mirrors the reference's test seams (SURVEY.md §4): the transfer plane and
router are tested engine-free; the full remote-prefill flow runs two real
tiny engines in one process (reference analogue:
examples/hello_world/disagg_skeleton + the vllm-patch flow in §3.3).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
from dynamo_tpu.llm.kv.transfer import (
    KvTransferClient,
    KvTransferServer,
    pack_blocks,
    unpack_blocks,
)
from dynamo_tpu.llm.protocols import (
    BackendInput,
    FinishReason,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.llm.workers import DecodeWorker, PrefillQueue, PrefillWorker, RemotePrefillRequest
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.models.loader import load_params_from_state_dict
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.transports.coordinator import CoordinatorClient, CoordinatorServer


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


async def until(cond, timeout_s=10.0):
    """Wait for ``cond()`` to a deadline: what was published reaches its
    watcher when the loop gets to it, which on a loaded machine is not
    within any fixed sleep."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout_s
    while not cond():
        assert loop.time() < deadline, "timed out waiting"
        await asyncio.sleep(0.01)


# ---------------------------------------------------------- transfer plane ----


def test_pack_unpack_roundtrip_bf16():
    import jax.numpy as jnp

    arr = np.asarray(jnp.arange(24, dtype=jnp.bfloat16).reshape(2, 3, 4))
    meta, data = pack_blocks(arr)
    out = unpack_blocks(meta, data)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert np.array_equal(np.asarray(out, np.float32), np.asarray(arr, np.float32))


def test_transfer_server_write_read_notify():
    async def go():
        store = np.zeros((2, 2, 8, 4, 6), np.float32)  # fake [L,2,N,Bs,D] pool
        notifications = []

        async def sink(block_ids, arr, request_id=None):
            store[:, :, block_ids] = arr

        async def source(block_ids):
            return store[:, :, block_ids]

        async def notify(rid, tok, err):
            notifications.append((rid, tok, err))

        srv = await KvTransferServer(sink, notify, source).start()
        try:
            client = await KvTransferClient.connect(srv.url)
            blocks = np.random.default_rng(0).standard_normal((2, 2, 3, 4, 6)).astype(
                np.float32
            )
            await client.write_blocks([1, 5, 2], blocks)
            assert np.array_equal(store[:, :, [1, 5, 2]], blocks)
            got = await client.read_blocks([5, 2])
            assert np.array_equal(got, store[:, :, [5, 2]])
            await client.notify("req-1", 42)
            assert notifications == [("req-1", 42, None)]
            await client.close()
        finally:
            await srv.stop()

    run(go())


# ------------------------------------------------------------ disagg router ----


def test_disagg_decision():
    r = DisaggregatedRouter(DisaggRouterConf(max_local_prefill_length=100,
                                             max_prefill_queue_size=2))
    assert r.prefill_remote(prefill_length=500, prefix_hit_length=0, queue_size=0)
    # prefix hit shrinks the effective prefill below threshold
    assert not r.prefill_remote(prefill_length=500, prefix_hit_length=450, queue_size=0)
    # deep queue forces local
    assert not r.prefill_remote(prefill_length=500, prefix_hit_length=0, queue_size=2)


def test_disagg_conf_hot_reload():
    async def go():
        srv = await CoordinatorServer(port=0).start()
        try:
            c = await CoordinatorClient(srv.url).connect()
            r = DisaggregatedRouter(namespace="ns1")
            await r.watch(c)
            assert r.conf.max_local_prefill_length == 512
            await r.publish(c, DisaggRouterConf(max_local_prefill_length=64,
                                                max_prefill_queue_size=4))
            await until(lambda: r.conf.max_local_prefill_length == 64)
            assert r.conf.max_prefill_queue_size == 4
            await c.close()
        finally:
            await srv.stop()

    run(go())


def test_prefill_queue_roundtrip():
    async def go():
        srv = await CoordinatorServer(port=0).start()
        try:
            c = await CoordinatorClient(srv.url).connect()
            q = PrefillQueue(c, "nsq")
            rpr = RemotePrefillRequest(
                request_id="r1", token_ids=[1, 2, 3], block_ids=[7, 8],
                skip_blocks=1, transfer_url="tcp://127.0.0.1:1",
                sampling=SamplingOptions(temperature=0.0),
            )
            await q.push(rpr)
            assert await q.size() == 1
            msg_id, got = await q.pull(timeout_s=1.0)
            assert got == rpr
            assert await q.size() == 1  # unacked still counts (backpressure)
            await q.ack(msg_id)
            assert await q.size() == 0
            await c.close()
        finally:
            await srv.stop()

    run(go())


# ------------------------------------------------------------- full e2e -------


@pytest.fixture(scope="module")
def setup():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        tie_word_embeddings=False,
    )
    hf = LlamaForCausalLM(hf_cfg).eval()
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())
    return model, params


def make_engine(model, params, mesh=None, cache_dtype=None):
    cfg = EngineConfig(
        max_batch_size=4,
        max_model_len=128,
        block_size=8,
        num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
        cache_dtype=cache_dtype,
    )
    return AsyncLLMEngine(EngineCore(model, params, cfg, mesh=mesh)).start()


async def _drain(engine_like, prompt, n):
    ctx = Context(
        BackendInput(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=n),
        )
    )
    toks = []
    async for out in engine_like.generate(ctx):
        toks.extend(out.token_ids)
        if out.finished:
            break
    return toks


@pytest.fixture()
def force_tcp(monkeypatch):
    """Pin the transfer plane to the wire path: these tests cover TCP/DCN
    framing; colocated engines would otherwise take the in-process ICI
    shortcut (covered separately by test_colocated_*)."""
    monkeypatch.setenv("DYN_KV_TRANSFER_FORCE_TCP", "1")


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_disagg_e2e_matches_local(setup, force_tcp, cache_dtype,
                                  monkeypatch):
    """Remote-prefill decode must produce exactly the local greedy tokens,
    including on a second request that hits the decode-side prefix cache
    (skip_blocks > 0 path).  With cache_dtype=int8 the transferred blocks
    are (data, scale) pairs end to end — quantized once on the prefill
    worker, moved bit-exactly, decoded against on the decode worker."""
    model, params = setup
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 128, size=30).tolist()

    # pin the wire format: int8 runs must actually move (int8 data, f32
    # scale) pairs — token equality alone would also pass a dequantizing
    # fallback
    import dynamo_tpu.llm.kv.transfer as tr

    payload_parts: list = []
    real_pack = tr.pack_blocks

    def spy_pack(arr):
        parts = list(arr) if isinstance(arr, (tuple, list)) else [arr]
        payload_parts.append([(np.asarray(p).dtype.name,) for p in parts])
        return real_pack(arr)

    async def go():
        monkeypatch.setattr(tr, "pack_blocks", spy_pack)
        srv = await CoordinatorServer(port=0).start()
        decode_engine = make_engine(model, params, cache_dtype=cache_dtype)
        prefill_engine = make_engine(model, params, cache_dtype=cache_dtype)
        reference_engine = make_engine(model, params, cache_dtype=cache_dtype)
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()

            worker = DecodeWorker(
                decode_engine,
                coordinator=c_dec,
                namespace="e2e",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0), namespace="e2e"
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "e2e")
            prefill_task = asyncio.ensure_future(prefill.run())

            expected = await _drain(reference_engine, prompt, 8)
            assert len(expected) == 8

            got = await _drain(worker, prompt, 8)
            assert got == expected
            assert prefill.handled == 1
            # prefill-side blocks were released after transfer
            assert prefill_engine.core._held == {}
            assert payload_parts, "no KV payload crossed the wire"
            if cache_dtype == "int8":
                assert payload_parts[0] == [("int8",), ("float32",)]
            else:
                assert payload_parts[0] == [("float32",)]

            # second identical request: decode-side prefix cache supplies the
            # full-block prefix; remainder (30-24=6 < any threshold... use
            # threshold 0 so it still goes remote) exercises skip_blocks>0
            got2 = await _drain(worker, prompt, 8)
            assert got2 == expected
            assert prefill.handled == 2

            # a short unique prompt with raised threshold stays local
            await worker.router.publish(
                c_dec, DisaggRouterConf(max_local_prefill_length=1000)
            )
            await until(
                lambda: worker.router.conf.max_local_prefill_length == 1000)
            prompt3 = rng.integers(1, 128, size=12).tolist()
            expected3 = await _drain(reference_engine, prompt3, 4)
            got3 = await _drain(worker, prompt3, 4)
            assert got3 == expected3
            assert prefill.handled == 2  # unchanged — handled locally

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            reference_engine.shutdown()
            await srv.stop()

    # a timeout of its own: on a loaded machine this test has waited on its
    # event loop for ever and held a whole run (alone it takes ~10 s)
    run(asyncio.wait_for(go(), timeout=180))


def test_disagg_sharded_decode_matches_local(setup, force_tcp):
    """Full disagg stack (coordinator + router + transfer) with a
    TP-SHARDED decode engine: the transfer-in scatter must reshard staged
    host blocks onto the mesh (each shard keeps its kv heads) and decode
    must still reproduce the local greedy tokens (VERDICT r2 weak #7)."""
    import jax
    from dynamo_tpu.utils.mesh import MESH_AXES, build_mesh

    model, params = setup
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, 128, size=28).tolist()
    mesh = build_mesh((1, 2), MESH_AXES)

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = make_engine(model, params, mesh=mesh)  # sharded
        prefill_engine = make_engine(model, params)            # unsharded
        reference_engine = make_engine(model, params)
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine, coordinator=c_dec, namespace="shard",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0),
                    namespace="shard",
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "shard")
            prefill_task = asyncio.ensure_future(prefill.run())

            expected = await _drain(reference_engine, prompt, 8)
            got = await _drain(worker, prompt, 8)
            assert got == expected
            assert prefill.handled == 1

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            reference_engine.shutdown()
            await srv.stop()

    run(go())


def test_colocated_handoff_skips_host_staging(setup, monkeypatch):
    """Colocated prefill/decode (same process) must move KV blocks
    device-to-device: no host gather, no wire serialization, and the
    scatter input stays a jax.Array (VERDICT r2 ask #8).  TCP remains the
    fallback for foreign URLs."""
    import jax

    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.llm.kv import transfer as tr

    model, params = setup
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, 128, size=30).tolist()

    staged = {"np_gathers": 0, "packs": 0, "scatter_types": []}
    real_gather_np = EngineCore.gather_blocks_np
    real_scatter = EngineCore.scatter_external
    real_pack = tr.pack_blocks

    def spy_gather_np(self, bids):
        staged["np_gathers"] += 1
        return real_gather_np(self, bids)

    def spy_scatter(self, bids, blocks, request_id=None):
        staged["scatter_types"].append(type(blocks).__name__)
        return real_scatter(self, bids, blocks, request_id)

    def spy_pack(arr):
        staged["packs"] += 1
        return real_pack(arr)

    monkeypatch.setattr(EngineCore, "gather_blocks_np", spy_gather_np)
    monkeypatch.setattr(EngineCore, "scatter_external", spy_scatter)
    monkeypatch.setattr(tr, "pack_blocks", spy_pack)

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = make_engine(model, params)
        prefill_engine = make_engine(model, params)
        reference_engine = make_engine(model, params)
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine, coordinator=c_dec, namespace="ici",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0), namespace="ici"
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "ici")
            prefill_task = asyncio.ensure_future(prefill.run())

            expected = await _drain(reference_engine, prompt, 8)
            got = await _drain(worker, prompt, 8)
            assert got == expected
            assert prefill.handled == 1

            # the handoff went device-to-device:
            assert staged["np_gathers"] == 0, "host staging on colocated path"
            assert staged["packs"] == 0, "wire serialization on colocated path"
            assert staged["scatter_types"], "scatter never ran"
            assert all(
                t != "ndarray" for t in staged["scatter_types"]
            ), f"scatter fed host arrays: {staged['scatter_types']}"

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            reference_engine.shutdown()
            await srv.stop()

    run(go())


def test_remote_prefill_cancellation(setup):
    """Aborting a stalled remote-prefill request frees its slot/blocks and
    a late notify is ignored."""
    model, params = setup

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = make_engine(model, params)
        try:
            c = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine,
                coordinator=c,
                namespace="cx",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0), namespace="cx"
                ),
            )
            await worker.start()  # no prefill worker → request stalls

            ctx = Context(
                BackendInput(
                    token_ids=list(range(1, 30)),
                    sampling=SamplingOptions(temperature=0.0),
                    stops=StopConditions(max_tokens=4),
                )
            )
            outs = []

            async def consume():
                async for out in worker.generate(ctx):
                    outs.append(out)

            task = asyncio.ensure_future(consume())
            await asyncio.sleep(0.3)
            assert await worker.queue.size() == 1  # enqueued, nobody pulling
            ctx.stop_generating()
            await asyncio.wait_for(task, timeout=5)
            assert outs and outs[-1].finish_reason is FinishReason.CANCELLED

            # late notify for the cancelled id is a no-op
            core = decode_engine.core
            await decode_engine.run_on_engine(
                lambda: core.complete_remote_prefill(ctx.id, 3)
            )
            # a late KV write for the cancelled id is dropped, not applied
            before = np.asarray(core.cache)
            stale = np.ones((2, 2, 1, 8, core.cache.shape[-1]), np.float32)
            await decode_engine.run_on_engine(
                lambda: core.scatter_external([0], stale, request_id=ctx.id)
            )
            assert np.array_equal(np.asarray(core.cache), before)
            # all blocks back in the pool
            assert core.block_manager.active_blocks == 0
            await worker.stop()
            await c.close()
        finally:
            decode_engine.shutdown()
            await srv.stop()

    run(go())


def test_disagg_json_mode_end_to_end(setup, force_tcp):
    """JSON mode across the disagg split: the prefill worker samples the
    grammar-masked first token, the decode worker continues the automaton
    from it (host advance on the transferred first token), and the final
    text parses as JSON."""
    import json as _json

    from dynamo_tpu.engine.grammar import JsonGrammar

    model, params = setup
    # byte-per-token vocab slice over the tiny model's 128-token vocab
    toks: list = [None] * 128
    for b in range(125):
        toks[3 + b] = bytes([b])
    EOS = 2
    grammar = JsonGrammar.from_token_bytes(toks, eos_ids=[EOS])

    def engine():
        cfg = EngineConfig(
            max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
            prefill_buckets=[16, 32, 64, 128],
        )
        return AsyncLLMEngine(EngineCore(
            model, params, cfg, eos_token_ids=[EOS], grammar=grammar
        )).start()

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = engine()
        prefill_engine = engine()
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine, coordinator=c_dec, namespace="jdis",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0),
                    namespace="jdis",
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "jdis")
            prefill_task = asyncio.ensure_future(prefill.run())

            ctx = Context(BackendInput(
                token_ids=list(range(5, 25)),
                sampling=SamplingOptions(temperature=1.0, json_mode=True),
                stops=StopConditions(max_tokens=40),
            ))
            outs = [o async for o in worker.generate(ctx)]
            assert prefill.handled == 1
            ids = [t for o in outs for t in o.token_ids]
            assert ids, outs
            raw = b"".join(toks[t] for t in ids if t != EOS and toks[t])
            if outs[-1].finish_reason is FinishReason.EOS:
                _json.loads(raw.decode("utf-8", errors="replace"))
            else:  # LENGTH: a valid JSON prefix — replay the automaton
                from dynamo_tpu.engine.grammar import INIT_STATE

                s, d, st = INIT_STATE, 0, 0
                for t in ids:
                    if t == EOS:
                        break
                    assert grammar.tables.valid_mask(s, d, st)[t]
                    s, d, st = grammar.tables.advance(s, d, st, t)

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            await srv.stop()

    run(go())


def test_disagg_decode_with_speculation(setup, force_tcp):
    """Prompt-lookup speculation on the DECODE worker composes with remote
    prefill: identical greedy tokens, fewer decode dispatches."""
    model, params = setup
    rng = np.random.default_rng(11)
    # a repetitive prompt gives the proposer material
    base_pat = rng.integers(1, 128, size=6).tolist()
    prompt = (base_pat * 4)[:22]

    def spec_engine():
        cfg = EngineConfig(
            max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
            prefill_buckets=[16, 32, 64, 128], spec_tokens=4,
        )
        return AsyncLLMEngine(EngineCore(model, params, cfg)).start()

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = spec_engine()
        prefill_engine = make_engine(model, params)
        reference_engine = make_engine(model, params)
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine, coordinator=c_dec, namespace="spdis",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0),
                    namespace="spdis",
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "spdis")
            prefill_task = asyncio.ensure_future(prefill.run())

            expected = await _drain(reference_engine, prompt, 10)
            got = await _drain(worker, prompt, 10)
            assert got == expected
            assert prefill.handled == 1

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            reference_engine.shutdown()
            await srv.stop()

    run(go())
