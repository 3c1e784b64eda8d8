"""dtspan tracing-plane tests (ISSUE 11).

Covers the tentpole seams: disabled-path overhead, span parenting +
wire inject/extract, the engine step timeline (phase sum accounts for
the step wall), Chrome trace-event export validity, measured transfer
costs, and the acceptance e2e — a seeded disagg request whose ONE
trace id stitches frontend task -> coordinator queue -> prefill
engine -> KV transfer -> decode engine.  The HTTP satellites
(x-request-id accept/echo, ITL histogram) run against the echo-engine
service from test_http_service.py.
"""

import asyncio
import json

import numpy as np
import pytest

from dynamo_tpu.obs import tracing
from dynamo_tpu.obs.costs import TransferCostTable, transfer_costs
from dynamo_tpu.obs.export import chrome_trace, trace_for_request
from dynamo_tpu.obs.metric_names import EngineMetric as EM, HttpMetric as HM
from dynamo_tpu.obs.timeline import PHASES, StepTimeline, step_timeline
from dynamo_tpu.runtime.transports.protocol import TRACE_FIELD


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture()
def traced():
    """Enable the tracing plane for one test, with full state restore."""
    was = tracing.enabled()
    tracing.enable(True)
    tracing.collector.reset()
    yield tracing
    tracing.enable(was)
    tracing.collector.reset()


# ------------------------------------------------------------ span core ----


def test_disabled_path_is_nop():
    """With tracing off, every entrypoint returns the preallocated
    singleton / None and touches nothing — the near-zero-overhead
    contract of the tentpole."""
    was = tracing.enabled()
    tracing.enable(False)
    try:
        tracing.collector.reset()
        s1 = tracing.start_span("x", attrs={"k": "v"})
        s2 = tracing.start_span("y")
        assert s1 is s2 is tracing.NOP_SPAN  # no allocation per call
        s1.set(a=1).end()
        assert tracing.current() is None
        header = {"op": "write_blocks"}
        assert tracing.inject(header) is header
        assert TRACE_FIELD not in header  # wire untouched when disabled
        assert tracing.extract({TRACE_FIELD: ["t", "s"]}) is None
        assert len(tracing.collector.spans) == 0
    finally:
        tracing.enable(was)


def test_span_parenting_and_contextvar(traced):
    root = tracing.start_span("root")
    assert root.parent_id is None
    assert tracing.current() == (root.trace_id, root.span_id)

    child = tracing.start_span("child")
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    child.end()
    # ending the child restores the parent as current
    assert tracing.current() == (root.trace_id, root.span_id)
    child.end()  # idempotent — double-end records once
    root.end()
    assert tracing.current() is None

    recs = tracing.collector.spans_for_trace(root.trace_id)
    assert [r["name"] for r in recs] == ["child", "root"]
    assert all(r["dur"] >= 0 for r in recs)

    # explicit parent= (cross-thread handoff) overrides the contextvar
    explicit = tracing.start_span("eng", parent=(root.trace_id, "abcd"))
    assert (explicit.trace_id, explicit.parent_id) == (root.trace_id, "abcd")
    explicit.end()


def test_inject_extract_roundtrip(traced):
    with tracing.start_span("rpc") as span:
        header = tracing.inject({"op": "queue_push"})
        assert header[TRACE_FIELD] == [span.trace_id, span.span_id]
        assert tracing.extract(header) == (span.trace_id, span.span_id)
    # malformed trace fields never raise — tracing must not take down
    # the data path
    for bad in (None, "x", [1, 2], ["only-one"], ["a", "b", "c"]):
        assert tracing.extract({TRACE_FIELD: bad}) is None
    # no active context -> nothing stamped
    assert TRACE_FIELD not in tracing.inject({"op": "p"})


def test_collector_bounded_and_request_binding(traced):
    c = tracing.Collector(maxlen=4, max_requests=2)
    for i in range(10):
        c.add({"trace": "t", "name": str(i)})
    assert len(c.spans) == 4  # ring, not unbounded
    c.bind_request("r1", "t1")
    c.bind_request("r2", "t2")
    c.bind_request("r3", "t3")
    assert c.trace_for_request("r1") is None  # FIFO-evicted
    assert c.trace_for_request("r3") == "t3"


# --------------------------------------------------------- step timeline ----


def test_timeline_phase_sum_accounts_wall():
    """Every instant of a step lies in exactly one open phase, so
    sum(phases) == wall to float rounding — well past the >=95 %
    acceptance bound."""
    import time

    tl = StepTimeline()
    t_start = time.perf_counter()
    tl.begin("admission")
    time.sleep(0.002)
    tl.enter("host_build")
    time.sleep(0.001)
    tl.enter("dispatch")
    time.sleep(0.003)
    tl.enter("readback")
    time.sleep(0.002)
    tl.enter("host_post")
    time.sleep(0.001)
    tl.end()
    wall_ub = time.perf_counter() - t_start

    snap = tl.snapshot()
    assert snap["steps_total"] == 1 and snap["busy_steps_total"] == 1
    wall = snap["wall_seconds_total"]
    assert 0.009 <= wall <= wall_ub
    phase_sum = sum(snap["phases"].values())
    assert phase_sum >= 0.95 * wall
    assert phase_sum == pytest.approx(wall, rel=1e-9)
    assert snap["phases"]["host_post"] > 0
    # host gap = wall - dispatch - readback
    gap_ms = (wall - snap["phases"]["dispatch"]
              - snap["phases"]["readback"]) * 1e3
    assert snap["host_gap_ms_per_turn"] == pytest.approx(gap_ms, rel=1e-6)


def test_timeline_idle_steps_excluded():
    tl = StepTimeline()
    tl.begin()
    tl.enter("host_ops")
    tl.end()  # no upload/dispatch/readback -> idle poll
    snap = tl.snapshot()
    assert snap["steps_total"] == 1
    assert snap["busy_steps_total"] == 0
    assert snap["wall_seconds_total"] == 0.0  # idle wall not banked
    # an enter outside begin/end (helper called from a unit test) is a
    # no-op
    tl.enter("dispatch", kind="step")
    assert tl.snapshot() == snap


# ----------------------------------------------------------- cost tables ----


def test_transfer_cost_table():
    t = TransferCostTable(alpha=0.5)
    t.record("a", "b", "dcn", 10_000_000, 0.1)  # 100 MB/s
    e = t.snapshot()[("a", "b", "dcn")]
    assert e["calls"] == 1 and e["bytes"] == 10_000_000
    assert e["ewma_mbps"] == pytest.approx(100.0)
    t.record("a", "b", "dcn", 10_000_000, 0.05)  # 200 MB/s sample
    e = t.snapshot()[("a", "b", "dcn")]
    assert e["calls"] == 2
    assert e["ewma_mbps"] == pytest.approx(150.0)  # 0.5*100 + 0.5*200
    # prediction uses the EWMA throughput
    assert t.cost_s("a", "b", "dcn", 15_000_000) == pytest.approx(0.1)
    # unmeasured edge falls back to the dtperf topology prior: finite,
    # positive, and exactly the derated-link formula
    from dynamo_tpu.obs.topology import prior_cost_s

    assert not t.measured("a", "b", "ici")
    prior = t.cost_s("a", "b", "ici", 1 << 20)
    assert prior == pytest.approx(prior_cost_s("ici", 1 << 20))
    assert 0 < prior < 1.0
    # unknown path names get the slowest (persist) prior, never free
    assert t.cost_s("a", "b", "???", 1 << 20) == pytest.approx(
        prior_cost_s("persist", 1 << 20))
    t.record("a", "b", "ici", 100, 0.0)  # zero-duration clamped, kept
    assert t.snapshot()[("a", "b", "ici")]["seconds"] > 0
    assert t.measured("a", "b", "ici")
    # a measured edge now uses the EWMA, not the prior
    assert t.cost_s("a", "b", "ici", 1 << 20) != pytest.approx(prior)


# --------------------------------------------------------- chrome export ----


def test_chrome_trace_export(traced):
    with tracing.start_span("outer", attrs={"request_id": "req-9"}) as outer:
        tracing.start_span("inner").end()
    tracing.collector.bind_request("req-9", outer.trace_id)

    doc = trace_for_request("req-9")
    assert doc is not None
    json.loads(json.dumps(doc))  # strictly JSON-serializable
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert e["cat"] == "dtspan"
        assert e["ts"] > 0 and e["dur"] >= 0  # wall-clock us
        assert isinstance(e["pid"], int) and e["tid"] == 1
        assert e["args"]["trace_id"] == outer.trace_id
    inner = next(e for e in xs if e["name"] == "inner")
    assert inner["args"]["parent_id"] == outer.span_id
    # span attrs ride along into args
    outer_ev = next(e for e in xs if e["name"] == "outer")
    assert outer_ev["args"]["request_id"] == "req-9"
    assert metas and metas[0]["name"] == "process_name"

    assert trace_for_request("never-seen") is None


# ------------------------------------------------- engine step timeline ----


@pytest.fixture(scope="module")
def setup():
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForCausalLM

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.models.loader import load_params_from_state_dict

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


def make_engine(model, params):
    from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore

    cfg = EngineConfig(
        max_batch_size=4,
        max_model_len=128,
        block_size=8,
        num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    return AsyncLLMEngine(EngineCore(model, params, cfg)).start()


async def _drain(engine_like, ctx):
    toks = []
    gen = engine_like.generate(ctx)
    try:
        async for out in gen:
            toks.extend(out.token_ids)
            if out.finished:
                break
    finally:
        # finalize on the live loop so the generator's cleanup (task
        # cancellation) runs before run() tears the loop down
        await gen.aclose()
    return toks


def _make_ctx(prompt, n):
    from dynamo_tpu.llm.protocols import (
        BackendInput,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    return Context(
        BackendInput(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=n),
        )
    )


def test_engine_step_timeline_accounts_wall(setup):
    """Acceptance: the instrumented EngineCore.step attributes >=95 % of
    busy-step wall time to named phases on a real generation."""
    model, params = setup
    step_timeline.reset()
    engine = make_engine(model, params)
    try:
        prompt = np.random.default_rng(3).integers(1, 128, size=20).tolist()
        toks = run(_drain(engine, _make_ctx(prompt, 6)))
        assert len(toks) == 6
    finally:
        engine.shutdown()

    snap = step_timeline.snapshot()
    assert snap["busy_steps_total"] >= 2  # >=1 prefill + >=1 decode step
    wall = snap["wall_seconds_total"]
    assert wall > 0
    assert sum(snap["phases"].values()) >= 0.95 * wall
    assert snap["phases"]["dispatch"] > 0
    assert set(snap["phases"]) == set(PHASES)
    assert snap["host_gap_ms_per_turn"] >= 0


# ------------------------------------------------- one-trace-id disagg e2e ----


@pytest.fixture()
def force_tcp(monkeypatch):
    """Pin the transfer plane to the wire path so the e2e exercises DCN
    framing + trace propagation (not the in-process ICI shortcut)."""
    monkeypatch.setenv("DYN_KV_TRANSFER_FORCE_TCP", "1")


def test_disagg_one_trace_id_e2e(setup, force_tcp, traced):
    """The acceptance path: a seeded disagg request (CPU devices,
    in-process coordinator) produces ONE trace whose spans cover the
    whole journey — frontend task, coordinator queue hop, prefill
    engine, KV transfer client+server, decode engine — and exports a
    valid Chrome trace via trace_for_request."""
    from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
    from dynamo_tpu.llm.workers import DecodeWorker, PrefillWorker
    from dynamo_tpu.runtime.transports.coordinator import (
        CoordinatorClient,
        CoordinatorServer,
    )

    model, params = setup
    transfer_costs.reset()
    prompt = np.random.default_rng(5).integers(1, 128, size=30).tolist()
    ctx = _make_ctx(prompt, 6)

    async def go():
        srv = await CoordinatorServer(port=0).start()
        decode_engine = make_engine(model, params)
        prefill_engine = make_engine(model, params)
        try:
            c_dec = await CoordinatorClient(srv.url).connect()
            c_pre = await CoordinatorClient(srv.url).connect()
            worker = DecodeWorker(
                decode_engine,
                coordinator=c_dec,
                namespace="obs",
                router=DisaggregatedRouter(
                    DisaggRouterConf(max_local_prefill_length=0),
                    namespace="obs",
                ),
            )
            await worker.start()
            prefill = PrefillWorker(prefill_engine, c_pre, "obs")
            prefill_task = asyncio.ensure_future(prefill.run())

            # the "frontend": a root span in the requesting task, as
            # HttpService._serve would open
            root = tracing.start_span("http.request",
                                      attrs={"request_id": ctx.id})
            toks = await _drain(worker, ctx)
            root.end()
            assert len(toks) == 6
            assert prefill.handled == 1
            # let the prefill side's spans land in the collector
            await asyncio.sleep(0.3)

            prefill.request_stop()
            await prefill_task
            await worker.stop()
            await c_dec.close()
            await c_pre.close()
            return root
        finally:
            decode_engine.shutdown()
            prefill_engine.shutdown()
            await srv.stop()

    root = run(go())

    spans = tracing.collector.spans_for_trace(root.trace_id)
    names = [s["name"] for s in spans]
    # one trace id covers every hop of the disagg path:
    assert "http.request" in names
    assert names.count("engine.generate") >= 2  # decode AND prefill engines
    assert "disagg.prefill" in names            # queue consumer, via rpr.trace
    assert "kv.write_blocks" in names           # prefill-side transfer client
    assert "kv.server.write_blocks" in names    # decode-side transfer server
    assert "kv.server.notify" in names
    assert any(n.startswith("coord.") for n in names)  # queue hop
    # the prefill-side spans are parented on the decode side's context
    dp = next(s for s in spans if s["name"] == "disagg.prefill")
    assert dp["parent"] is not None

    # the KV hop went over the wire and was measured as a DCN edge
    dcn = [k for k in transfer_costs.snapshot() if k[2] == "dcn"]
    assert dcn, "forced-TCP transfer left no measured dcn edge"
    assert all(v["bytes"] > 0 and v["seconds"] > 0
               for v in transfer_costs.snapshot().values())

    # request-id -> Chrome export (what /debug/traces/{rid} serves)
    doc = trace_for_request(ctx.id)
    assert doc is not None
    json.loads(json.dumps(doc))
    evnames = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"http.request", "disagg.prefill", "kv.write_blocks"} <= evnames


# --------------------------------------------------- HTTP satellites ----


WORDS = ["hello", "world", "foo", "bar", "baz", "stop", "the", "quick"]


@pytest.fixture(scope="module")
def card(tmp_path_factory):
    pytest.importorskip("tokenizers")
    from tokenizers import Tokenizer, models, pre_tokenizers

    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    for w in WORDS + ["<|user|>", "<|assistant|>", "<|system|>"]:
        vocab[w] = len(vocab)
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    path = tmp_path_factory.mktemp("obs_tok") / "tokenizer.json"
    tok.save(str(path))
    return ModelDeploymentCard(
        name="echo-model", tokenizer_path=str(path), context_length=128
    )


async def _start_service(card):
    from dynamo_tpu.llm.engines import EchoEngineCore, build_serving_pipeline
    from dynamo_tpu.llm.http import HttpService, ModelManager

    manager = ModelManager()
    manager.add_model(
        "echo-model", build_serving_pipeline(EchoEngineCore(), card), card
    )
    svc = HttpService(manager, port=0)
    await svc.start()
    return svc


def test_http_request_id_echo_and_itl(card):
    """Satellites: x-request-id is accepted and echoed on both unary and
    streaming responses; the ITL histogram appears on /metrics after a
    streamed generation; /debug/traces 404s helpfully when untraced."""
    from aiohttp import ClientSession

    async def go():
        svc = await _start_service(card)
        try:
            base = f"http://127.0.0.1:{svc.port}"
            async with ClientSession() as s:
                r = await s.post(
                    f"{base}/v1/completions",
                    json={"model": "echo-model", "prompt": "hello world",
                          "max_tokens": 8},
                    headers={"x-request-id": "cli-abc-1"},
                )
                assert r.status == 200
                assert r.headers.get("x-request-id") == "cli-abc-1"

                r = await s.post(
                    f"{base}/v1/completions",
                    json={"model": "echo-model", "prompt": "the quick foo bar",
                          "max_tokens": 8, "stream": True},
                    headers={"x-request-id": "cli-abc-2"},
                )
                assert r.status == 200
                assert r.headers.get("x-request-id") == "cli-abc-2"
                await r.read()

                # no header sent -> none echoed
                r = await s.post(
                    f"{base}/v1/completions",
                    json={"model": "echo-model", "prompt": "baz",
                          "max_tokens": 4},
                )
                assert r.status == 200
                assert "x-request-id" not in r.headers

                m = await s.get(f"{base}/metrics")
                text = await m.text()
                assert f"{HM.INTER_TOKEN_SECONDS}_bucket" in text
                assert (f'{HM.INTER_TOKEN_SECONDS}_count'
                        '{model="echo-model"}') in text
                # step timeline block renders even with a non-EngineCore
                # backend (zeros are fine — the names are the contract)
                assert EM.HOST_GAP_MS_PER_TURN in text

                r = await s.get(f"{base}/debug/traces/cli-abc-1")
                assert r.status == 404
                body = await r.json()
                assert "DYNAMO_TRACE" in body["error"]
        finally:
            await svc.stop()

    run(go())
