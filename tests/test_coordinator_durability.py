"""Coordinator durability + client reconnect (VERDICT r2 ask #7).

A coordinator restart must lose no queued remote prefill or unleased KV
(WAL replay; ref raft-backed etcd transports/etcd.rs:40-255 + JetStream
file store), and reconnect-enabled clients must re-register their watches,
subscriptions, leases, and lease-bound keys so discovery heals.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.runtime.transports.coordinator import (
    CoordinatorClient,
    CoordinatorServer,
)


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_wal_replay_kv_and_queue(tmp_path):
    async def go():
        srv = await CoordinatorServer(data_dir=str(tmp_path)).start()
        port = srv.port
        c = await CoordinatorClient(srv.url).connect()
        await c.kv_put("cfg/a", {"x": 1})
        await c.kv_put("cfg/b", "bee")
        await c.kv_delete("cfg/b")
        lease = await c.lease_create(ttl=30, auto_keepalive=False)
        await c.kv_put("ephemeral/worker1", "alive", lease_id=lease)
        m1 = await c.queue_push("work", b"job-1")
        await c.queue_push("work", b"job-2")
        await c.queue_push("work", b"job-3")
        # pull+ack one, pull-without-ack another (must redeliver post-restart)
        mid, payload = await c.queue_pull("work")
        assert payload == b"job-1"
        await c.queue_ack("work", mid)
        await c.queue_pull("work")  # job-2 delivered, never acked
        await c.close()
        await srv.stop()

        srv2 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()
        c2 = await CoordinatorClient(srv2.url).connect()
        assert await c2.kv_get("cfg/a") == {"x": 1}
        assert await c2.kv_get("cfg/b") is None
        # lease-bound key died with its owner (by design)
        assert await c2.kv_get("ephemeral/worker1") is None
        # unacked + unpulled jobs survive, in order; acked one does not
        got = []
        for _ in range(3):
            item = await c2.queue_pull("work", timeout_s=0.2)
            if item is None:
                break
            got.append(item[1])
            await c2.queue_ack("work", item[0])
        assert got == [b"job-2", b"job-3"]
        await c2.close()
        await srv2.stop()

        # third boot: compaction kept acked jobs gone and kv intact
        srv3 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()
        c3 = await CoordinatorClient(srv3.url).connect()
        assert await c3.kv_get("cfg/a") == {"x": 1}
        assert await c3.queue_pull("work", timeout_s=0.1) is None
        await c3.close()
        await srv3.stop()

    run(go())


def test_client_reconnect_reregisters(tmp_path):
    async def go():
        srv = await CoordinatorServer(data_dir=str(tmp_path)).start()
        port = srv.port
        worker = await CoordinatorClient(srv.url, reconnect=True).connect()
        events: list[tuple[str, str]] = []
        await worker.watch("disc/", lambda e, k, v: events.append((e, k)))
        lease = await worker.lease_create(ttl=5.0)
        await worker.kv_put("disc/worker-7", {"addr": "w7:1"}, lease_id=lease)
        subs: list[str] = []
        await worker.subscribe("events.>", lambda s, p: subs.append(s))

        # coordinator dies and comes back on the same port
        await srv.stop()
        srv2 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()

        # reconnect + re-registration is automatic
        for _ in range(100):
            await asyncio.sleep(0.05)
            if worker._reconnect_task and worker._reconnect_task.done():
                break
        other = await CoordinatorClient(srv2.url).connect()
        # lease-bound discovery key re-registered under a fresh lease
        assert await other.kv_get("disc/worker-7") == {"addr": "w7:1"}
        # subscription works again
        delivered = await other.publish("events.kv", b"hi")
        assert delivered == 1
        # watch callback fires again for new keys
        await other.kv_put("disc/worker-9", {"addr": "w9:1"})
        await asyncio.sleep(0.2)
        assert any(k == "disc/worker-9" for _, k in events)
        # keepalive keeps the NEW lease alive (old id invalid): key persists
        await asyncio.sleep(0.5)
        assert await other.kv_get("disc/worker-7") == {"addr": "w7:1"}
        await other.close()
        await worker.close()
        await srv2.stop()

    run(go())


def test_reconnect_synthesizes_deletes_for_vanished_keys(tmp_path):
    """Keys that disappeared during the outage (e.g. a worker that crashed
    while the coordinator was down) must surface as delete events after
    reconnect, or routers keep routing to dead instances."""
    async def go():
        srv = await CoordinatorServer(data_dir=str(tmp_path)).start()
        port = srv.port
        watcher = await CoordinatorClient(srv.url, reconnect=True).connect()
        dead = await CoordinatorClient(srv.url).connect()  # no reconnect
        events: list[tuple[str, str]] = []
        await watcher.watch("w/", lambda e, k, v: events.append((e, k)))
        lease = await dead.lease_create(ttl=30, auto_keepalive=False)
        await dead.kv_put("w/dead-worker", "addr", lease_id=lease)
        await asyncio.sleep(0.1)
        assert ("put", "w/dead-worker") in events

        await srv.stop()       # outage begins
        await dead.close()     # ...and the worker dies during it
        srv2 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()
        for _ in range(100):
            await asyncio.sleep(0.05)
            if ("delete", "w/dead-worker") in events:
                break
        assert ("delete", "w/dead-worker") in events
        await watcher.close()
        await srv2.stop()

    run(go())


def test_lease_transitions_do_not_resurrect(tmp_path):
    """(a) A durable key later bound to a lease must NOT replay its old
    durable value after restart; (b) keys of a revoked lease must not be
    re-put by the reconnecting client."""
    async def go():
        srv = await CoordinatorServer(data_dir=str(tmp_path)).start()
        port = srv.port
        c = await CoordinatorClient(srv.url, reconnect=True).connect()
        # (a) durable → leased transition
        await c.kv_put("cfg/x", "v1")
        lease = await c.lease_create(ttl=30)
        await c.kv_put("cfg/x", "v2", lease_id=lease)
        # (b) a leased key whose lease is revoked before the restart
        lease2 = await c.lease_create(ttl=30)
        await c.kv_put("cfg/y", "ephemeral", lease_id=lease2)
        await c.lease_revoke(lease2)
        assert await c.kv_get("cfg/y") is None

        await srv.stop()
        srv2 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()
        for _ in range(100):
            await asyncio.sleep(0.05)
            if c._reconnect_task and c._reconnect_task.done():
                break
        other = await CoordinatorClient(srv2.url).connect()
        # x: v1 must not resurrect; the reconnecting client re-put v2 (leased)
        assert await other.kv_get("cfg/x") == "v2"
        # y: revoked — gone for good
        assert await other.kv_get("cfg/y") is None
        await other.close()
        await c.close()
        await srv2.stop()

    run(go())


def test_heal_cedes_create_exclusive_key_to_new_owner(tmp_path):
    """A kv_create-established key whose lease expired server-side may
    have been legitimately claimed by another process before the heal
    runs — the heal must re-acquire with create-exclusivity and CEDE on
    conflict, never silently overwrite the new owner's value (while
    plain kv_put keys still re-put unconditionally)."""
    async def go():
        srv = await CoordinatorServer().start()
        a = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease = await a.lease_create(ttl=30)
        assert await a.kv_create("svc/leader", "A", lease_id=lease)
        await a.kv_put("svc/info", "a-info", lease_id=lease)

        # server-side expiry: revoke through a raw second client so A's
        # bookkeeping still believes the lease (and its keys) are live
        raw = await CoordinatorClient(srv.url).connect()
        await raw._call({"op": "lease_revoke", "lease_id": lease})
        assert await raw.kv_get("svc/leader") is None

        # another process claims leadership in the expiry window
        b = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease_b = await b.lease_create(ttl=30)
        assert await b.kv_create("svc/leader", "B", lease_id=lease_b)

        await a._heal_expired_lease(lease, 30.0)
        # the create-exclusive key ceded to B; the put key healed back
        assert await raw.kv_get("svc/leader") == "B"
        assert await raw.kv_get("svc/info") == "a-info"
        assert "svc/leader" not in a._leased_kv  # no re-put on reconnect

        for c in (a, b, raw):
            await c.close()
        await srv.stop()

    run(go())


def test_heal_reacquires_create_exclusive_key_when_unclaimed(tmp_path):
    """The common heal case: nobody claimed the expired key, so the
    create-exclusive re-acquire succeeds and the key stays bound."""
    async def go():
        srv = await CoordinatorServer().start()
        a = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease = await a.lease_create(ttl=30)
        assert await a.kv_create("svc/leader", "A", lease_id=lease)
        raw = await CoordinatorClient(srv.url).connect()
        await raw._call({"op": "lease_revoke", "lease_id": lease})
        await a._heal_expired_lease(lease, 30.0)
        assert await raw.kv_get("svc/leader") == "A"
        assert "svc/leader" in a._leased_kv
        await a.close()
        await raw.close()
        await srv.stop()

    run(go())


def test_reregister_cedes_created_key_to_new_owner(tmp_path):
    """The reconnect path has the same ownership race as the heal path:
    if the outage outlived the lease TTL and another process claimed a
    kv_create-established key, re-registration must cede, not overwrite."""
    async def go():
        srv = await CoordinatorServer().start()
        a = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease = await a.lease_create(ttl=30)
        assert await a.kv_create("svc/leader", "A", lease_id=lease)
        raw = await CoordinatorClient(srv.url).connect()
        await raw._call({"op": "lease_revoke", "lease_id": lease})
        b = await CoordinatorClient(srv.url, reconnect=True).connect()
        lb = await b.lease_create(ttl=30)
        assert await b.kv_create("svc/leader", "B", lease_id=lb)
        await a._reregister()
        assert await raw.kv_get("svc/leader") == "B"
        assert "svc/leader" not in a._leased_kv
        for c in (a, b, raw):
            await c.close()
        await srv.stop()

    run(go())


def test_reregister_takes_over_own_stale_created_key(tmp_path):
    """Brief-drop case: the server still holds OUR old binding (same
    value) under the soon-to-expire old lease — re-registration rebinds
    it to the fresh lease instead of wrongly ceding our own key."""
    async def go():
        srv = await CoordinatorServer().start()
        a = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease = await a.lease_create(ttl=30)
        assert await a.kv_create("svc/leader", "A", lease_id=lease)
        await a._reregister()  # old key still present with our value
        raw = await CoordinatorClient(srv.url).connect()
        assert await raw.kv_get("svc/leader") == "A"
        assert "svc/leader" in a._leased_kv
        await a.close()
        await raw.close()
        await srv.stop()

    run(go())


def test_kv_put_update_preserves_create_exclusivity(tmp_path):
    """Updating a kv_create-established key's value with kv_put must not
    erase its ownership record — a later heal would otherwise blindly
    overwrite a new owner."""
    async def go():
        srv = await CoordinatorServer().start()
        a = await CoordinatorClient(srv.url, reconnect=True).connect()
        lease = await a.lease_create(ttl=30)
        assert await a.kv_create("svc/leader", "A-v1", lease_id=lease)
        await a.kv_put("svc/leader", "A-v2", lease_id=lease)
        assert a._leased_kv["svc/leader"][2] is True
        # expiry + rival claim: the heal must still cede
        raw = await CoordinatorClient(srv.url).connect()
        await raw._call({"op": "lease_revoke", "lease_id": lease})
        b = await CoordinatorClient(srv.url, reconnect=True).connect()
        lb = await b.lease_create(ttl=30)
        assert await b.kv_create("svc/leader", "B", lease_id=lb)
        await a._heal_expired_lease(lease, 30.0)
        assert await raw.kv_get("svc/leader") == "B"
        for c in (a, b, raw):
            await c.close()
        await srv.stop()

    run(go())


def test_calls_fail_fast_while_disconnected(tmp_path):
    async def go():
        srv = await CoordinatorServer().start()
        c = await CoordinatorClient(srv.url, reconnect=True).connect()
        await srv.stop()
        await asyncio.sleep(0.1)
        with pytest.raises(ConnectionError):
            await c.kv_get("anything")
        await c.close()

    run(go())


def test_disagg_queued_prefill_survives_restart(tmp_path):
    """Kill-and-restart the coordinator mid-disagg: a remote prefill pushed
    before the crash redelivers from the WAL and completes after restart."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
    from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
    from dynamo_tpu.llm.protocols import BackendInput, SamplingOptions, StopConditions
    from dynamo_tpu.llm.workers import DecodeWorker, PrefillWorker
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.models.loader import load_params_from_state_dict
    from dynamo_tpu.runtime.engine import Context

    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256,
    )
    hf = LlamaForCausalLM(hf_cfg).eval()
    cfg = ModelConfig.from_hf_config(hf_cfg.to_dict(), dtype="float32")
    model = LlamaModel(cfg)
    params = load_params_from_state_dict(cfg, hf.state_dict())

    def make_engine():
        return AsyncLLMEngine(EngineCore(model, params, EngineConfig(
            max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
            prefill_buckets=[16, 32, 64, 128],
        ))).start()

    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 128, size=26).tolist()

    async def drain(engine_like, prompt, n):
        ctx = Context(BackendInput(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=n),
        ))
        toks = []
        async for out in engine_like.generate(ctx):
            toks.extend(out.token_ids)
            if out.finished:
                break
        return toks

    async def go():
        srv = await CoordinatorServer(data_dir=str(tmp_path)).start()
        port = srv.port
        decode_engine = make_engine()
        prefill_engine = make_engine()
        reference_engine = make_engine()
        try:
            c_dec = await CoordinatorClient(srv.url, reconnect=True).connect()
            worker = DecodeWorker(
                decode_engine, coordinator=c_dec, namespace="dur",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0), namespace="dur"
                ),
            )
            await worker.start()
            expected = await drain(reference_engine, prompt, 6)

            # request stalls in REMOTE_PREFILL (no prefill worker yet).
            # Wait for its queue push to reach the coordinator, and so the
            # WAL, not for a fixed time: on a loaded machine the push can
            # take longer than any sleep, and a push into the stopped
            # coordinator is a ConnectionError, not a redelivery
            task = asyncio.ensure_future(drain(worker, prompt, 6))
            for _ in range(1200):
                if any(srv._queues.values()) or task.done():
                    break
                await asyncio.sleep(0.05)
            assert any(srv._queues.values()), "prefill was never queued"
            assert not task.done()

            # coordinator crashes and restarts
            await srv.stop()
            srv2 = await CoordinatorServer(port=port, data_dir=str(tmp_path)).start()

            # prefill worker arrives after the crash: the queued request
            # must redeliver from the WAL and complete the stalled decode
            c_pre = await CoordinatorClient(srv2.url, reconnect=True).connect()
            prefill = PrefillWorker(prefill_engine, c_pre, "dur")
            prefill_task = asyncio.ensure_future(prefill.run())

            got = await asyncio.wait_for(task, timeout=60)
            assert got == expected
            assert prefill.handled == 1

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
            await srv2.stop()
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            reference_engine.shutdown()

    run(go())
