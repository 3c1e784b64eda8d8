"""PR 43: a request's stages as spans of the dtspan plane, and the front
end's two ends (docs/observability.md, "A request's stages").  With the plane
off a request makes no span object; with it on ``/debug/traces/<id>`` holds
the four ``engine.*`` stages in order inside ``engine.generate``, and
``engine.step`` spans hang under the timeline's own trace; ``pre_submit`` and
``emit_lag`` are on ``/metrics`` after one streamed and one unary request."""

import asyncio
import json

import jax
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs import tracing
from dynamo_tpu.obs.metric_names import EngineMetric as EM
from dynamo_tpu.obs.metric_names import HttpMetric as HM
from dynamo_tpu.obs.timeline import step_timeline
from test_request_stages import make_core, run_dry, submit
from test_request_stages import tiny  # noqa: F401  (fixture)

STAGES = ["engine.queue", "engine.turn_wait", "engine.prefill",
          "engine.decode"]


# ------------------------------------------------------------ the span plane
@pytest.fixture()
def plane():
    """The dtspan plane as the test asks for it, restored afterwards."""
    was = tracing.enabled()
    tracing.collector.reset()

    def switch(on):
        tracing.enable(on)
        return tracing

    yield switch
    tracing.enable(was)
    tracing.collector.reset()


def test_with_the_plane_off_a_request_makes_no_span(tiny, plane, monkeypatch):
    plane(False)

    def refuse(*a, **kw):
        raise AssertionError("a span object was made with the plane off")

    monkeypatch.setattr(tracing.Span, "__init__", refuse)
    monkeypatch.setattr(tracing, "record_span", refuse)
    monkeypatch.setattr(tracing, "new_trace_id", refuse)
    step_timeline.reset()
    core = make_core(*tiny)
    span = tracing.start_span("engine.generate")
    assert span is tracing.NOP_SPAN and span.context() is None
    req, outs = submit(core, "quiet", 40, 4, trace=span.context())
    run_dry(core)
    assert sum(len(o.token_ids) for o in outs) == 4 and req.first_token_at
    assert len(tracing.collector.spans) == 0
    assert tracing.collector.spans_for_trace(tracing.ENGINE_TRACE) == []


def test_with_the_plane_on_the_engine_adds_the_four_stages(tiny, plane):
    plane(True)
    step_timeline.reset()
    core = make_core(*tiny)
    root = tracing.start_span("engine.generate")
    req, _ = submit(core, "loud", 40, 4, trace=root.context())
    run_dry(core)
    root.end()
    spans = tracing.collector.spans_for_trace(root.trace_id)
    assert [s["name"] for s in spans] == STAGES + ["engine.generate"]
    by = {s["name"]: s for s in spans}
    assert all(by[n]["parent"] == root.span_id for n in STAGES)
    # each starts where the one before it ends, on monotonic_ns's axis
    for a, b in zip(STAGES, STAGES[1:]):
        assert abs(by[a]["ts"] + by[a]["dur"] - by[b]["ts"]) <= 1
    assert by["engine.queue"]["ts"] == int(req.submitted_at * 1e9)
    assert by["engine.prefill"]["attrs"] == {
        "chunks": 3, "prompt_tokens": 40, "cached_tokens": 0,
        "first_step": req.first_issue_step, "last_step": req.first_token_step}
    dec = by["engine.decode"]["attrs"]
    assert dec["tokens"] == 4 and dec["first_step"] == req.first_token_step
    assert dec["first_step"] <= dec["last_step"] \
        <= step_timeline.busy_steps_total
    # the steps are the engine's own trace: one id, no parent, numbered
    steps = [s for s in tracing.collector.spans if s["name"] == "engine.step"]
    assert len(steps) == step_timeline.busy_steps_total
    assert {s["trace"] for s in steps} == {tracing.ENGINE_TRACE}
    assert steps == tracing.collector.spans_for_trace(
        tracing.collector.trace_for_request(tracing.ENGINE_TRACE))
    assert all(s["parent"] is None for s in steps)
    assert [s["attrs"]["step"] for s in steps] == list(range(len(steps)))
    assert not hasattr(core, "_active_trace")


# ------------------------------------------------------------- the front end
WORDS = ["a", "b", "c", "d", "e", "f", "g", "h", "i", "j", "k", "l"]


@pytest.fixture(scope="module")
def tokenizer_file(tmp_path_factory):
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"<unk>": 0}
    for w in WORDS + ["<|user|>", "<|assistant|>", "<|system|>"]:
        vocab[w] = len(vocab)
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    path = tmp_path_factory.mktemp("tok") / "tokenizer.json"
    tok.save(str(path))
    return str(path), len(vocab)


def serve(tokenizer_file, client):
    """The whole stack in one process; ``client(session, base, svc)``."""
    from aiohttp import ClientSession

    from dynamo_tpu.llm.engines import build_serving_pipeline
    from dynamo_tpu.llm.http import HttpService, ModelManager
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    tok_path, vocab_size = tokenizer_file

    async def go():
        model = LlamaModel(ModelConfig.tiny(vocab_size=vocab_size))
        params = await asyncio.to_thread(
            model.init_params, jax.random.PRNGKey(0))
        core = EngineCore(model, params, EngineConfig(
            max_batch_size=4, max_model_len=64, block_size=8, num_blocks=32,
            prefill_buckets=[16, 32, 64]))
        eng = AsyncLLMEngine(core).start()
        card = ModelDeploymentCard(name="tiny", tokenizer_path=tok_path,
                                   context_length=64)
        manager = ModelManager()
        manager.add_model("tiny", build_serving_pipeline(eng, card), card)
        svc = HttpService(manager, port=0)
        await svc.start()
        try:
            async with ClientSession() as s:
                return await client(s, f"http://127.0.0.1:{svc.port}", svc)
        finally:
            await svc.stop()
            eng.shutdown()

    return asyncio.new_event_loop().run_until_complete(go())


def completion(stream, rid):
    return dict(json={"model": "tiny", "prompt": "a b c d e f", "max_tokens": 5,
                      "temperature": 0, "stream": stream},
                headers={"x-request-id": rid})


def test_pre_submit_and_emit_lag_are_on_metrics(tokenizer_file):
    async def client(s, base, svc):
        r = await s.post(f"{base}/v1/completions", **completion(True, "s-1"))
        assert r.status == 200
        await r.read()
        streamed = svc.metrics.emit_lag["tiny"].n
        r = await s.post(f"{base}/v1/completions", **completion(False, "u-1"))
        assert r.status == 200 and (await r.json())["usage"][
            "completion_tokens"] == 5
        return streamed, svc.metrics, await (await s.get(f"{base}/metrics")).text()

    streamed, metrics, text = serve(tokenizer_file, client)
    # a streamed request observes at every chunk it writes, a unary one once
    assert streamed == 5 and metrics.emit_lag["tiny"].n == 6
    assert metrics.pre_submit["tiny"].n == 2
    assert 0 < metrics.pre_submit["tiny"].total < 5
    assert 0 < metrics.emit_lag["tiny"].total < 5
    for name, n in ((HM.PRE_SUBMIT_SECONDS, 2), (HM.EMIT_LAG_SECONDS, 6)):
        assert f"# TYPE {name} histogram" in text
        assert f'{name}_count{{model="tiny"}} {n}' in text
    # the emit-lag ladder is the inter-token one
    assert f'{HM.EMIT_LAG_SECONDS}_bucket{{model="tiny",le="0.001"}}' in text
    for name in (EM.TURN_WAIT_SECONDS_TOTAL, EM.PREFILL_SPAN_SECONDS_TOTAL,
                 EM.PREFILL_READY_ROWS_TOTAL):
        assert f"# TYPE {name} counter" in text
    for name in (EM.STEP_CLASS_LAUNCH_SECONDS_TOTAL,
                 EM.STEP_CLASS_READBACK_SECONDS_TOTAL):
        assert f'{name}{{class="decode"}}' in text


@pytest.mark.parametrize("stream", [True, False], ids=["streamed", "unary"])
def test_debug_traces_holds_a_request_s_whole_life(tokenizer_file, plane,
                                                   stream):
    plane(True)

    async def client(s, base, svc):
        r = await s.post(f"{base}/v1/completions", **completion(stream, "t-1"))
        assert r.status == 200
        await r.read()
        # the engine thread closes engine.decode right after the emit of
        # the last token, and engine.generate ends when the pipeline lets
        # go of the stream: both may trail the response by a moment
        for _ in range(100):
            r = await s.get(f"{base}/debug/traces/t-1")
            assert r.status == 200
            doc = json.loads(await r.text())
            if {"engine.generate", "engine.decode"} <= {
                    e["name"] for e in doc["traceEvents"]}:
                break
            await asyncio.sleep(0.05)
        return doc

    doc = serve(tokenizer_file, client)
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in events]
    assert "engine.step" not in names
    assert {"http.request", "engine.generate", *STAGES} <= set(names)
    by = {e["name"]: e for e in events}
    assert len({e["args"]["trace_id"] for e in events}) == 1
    gen = by["engine.generate"]
    assert gen["args"]["parent_id"] == by["http.request"]["args"]["span_id"]
    starts = [by[n]["ts"] for n in STAGES]
    assert starts == sorted(starts)                 # in order
    for n in STAGES:                                # each inside engine.generate
        assert by[n]["args"]["parent_id"] == gen["args"]["span_id"]
        assert gen["ts"] - 1 <= by[n]["ts"]
        assert by[n]["ts"] + by[n]["dur"] <= gen["ts"] + gen["dur"] + 1
    assert by["engine.prefill"]["args"]["prompt_tokens"] == 6
    assert by["engine.decode"]["args"]["tokens"] == 5


def test_debug_traces_engine_holds_the_steps_and_the_counter_track(
        tokenizer_file, plane):
    """The steps left the requests' traces and stay within reach: one fixed
    name fetches them, with the dtperf predicted-vs-measured track."""
    plane(True)
    step_timeline.reset()

    async def client(s, base, svc):
        r = await s.get(f"{base}/debug/traces/engine")
        assert r.status == 404                      # no step ran yet
        # a client's own id of that name does not take the name over
        r = await s.post(f"{base}/v1/completions",
                         **completion(True, tracing.ENGINE_TRACE))
        assert r.status == 200
        await r.read()
        r = await s.get(f"{base}/debug/traces/engine")
        assert r.status == 200
        return json.loads(await r.text())

    doc = serve(tokenizer_file, client)
    steps = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert steps and {e["name"] for e in steps} == {"engine.step"}
    assert {e["args"]["trace_id"] for e in steps} == {tracing.ENGINE_TRACE}
    numbers = [e["args"]["step"] for e in steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert all("parent_id" not in e["args"] for e in steps)
    track = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(track) == len(steps) and {e["cat"] for e in track} == {"dtperf"}
    assert all(e["args"]["measured"] > 0 for e in track)
    assert any("predicted" in e["args"] for e in track)
