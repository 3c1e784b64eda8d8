"""Token-budget ragged prefill: packing many sequences' prefill chunks
into one dispatch must be invisible to callers — parity against the
legacy one-request-per-dispatch path (tokens, logprobs, cached_tokens),
the dispatch-count win, and prefix-join semantics under batching."""

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.grammar import JsonGrammar
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

EOS = 2
BS = 8  # block size used throughout


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=320, hidden_size=32, intermediate_size=64,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_position_embeddings=256, rope_theta=10000.0, dtype="float32",
    )
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # byte-complete vocab so JSON mode can always make progress
    toks: list = [None] * 320
    for b in range(256):
        toks[3 + b] = bytes([b])
    grammar = JsonGrammar.from_token_bytes(toks, eos_ids=[EOS])
    return model, params, grammar


def make_core(model, params, grammar=None, **kw):
    cfg = EngineConfig(
        max_batch_size=8,
        max_model_len=256,
        block_size=BS,
        num_blocks=128,
        prefill_buckets=[16, 32, 64, 128, 256],
        **kw,
    )
    return EngineCore(model, params, cfg, eos_token_ids=[EOS],
                      grammar=grammar)


def drain(core, budget=3000):
    for _ in range(budget):
        if not core.step():
            break


def mixed_requests():
    """The ISSUE's seeded mixed batch: one long prompt that stays
    mid-chunk across dispatches, two short final-chunk prompts — one with
    grammar, one with top_logprobs — plus a plain greedy one."""
    rng = np.random.RandomState(42)
    p = lambda n, lo=3: list(rng.randint(lo, 259, size=n))
    return [
        ("long", p(44), SamplingOptions(temperature=1.0, seed=7),
         StopConditions(max_tokens=3)),
        ("json", p(8), SamplingOptions(temperature=0.0, json_mode=True),
         StopConditions(max_tokens=8)),
        ("lp", p(10),
         SamplingOptions(temperature=0.9, seed=123, logprobs=True,
                         top_logprobs=3),
         StopConditions(max_tokens=3)),
        ("plain", p(9), SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=3)),
    ]


def run_requests(core, specs, sequential):
    outs = {name: [] for name, *_ in specs}
    reqs = [
        EngineRequest(name, list(prompt), sampling, stops,
                      emit=outs[name].append)
        for name, prompt, sampling, stops in specs
    ]
    if sequential:
        for r in reqs:
            core.submit(r)
            drain(core)
    else:
        for r in reqs:
            core.submit(r)
        drain(core)
    return outs


def flat(outs, field="token_ids"):
    return [x for o in outs for x in (getattr(o, field) or [])]


@pytest.fixture(scope="module")
def sequential_reference(setup):
    """The legacy-path reference: the mixed requests prefilled one at a
    time with batching disabled (prefill_token_budget=0)."""
    model, params, grammar = setup
    return run_requests(
        make_core(model, params, grammar, prefill_chunk_tokens=16),
        mixed_requests(), sequential=True)


def test_mixed_batch_parity(setup, sequential_reference):
    """Batched prefill output is identical to the same requests run
    sequentially with batching disabled: tokens, finish reasons, logprobs,
    top_logprobs and cached_tokens accounting."""
    model, params, grammar = setup
    specs = mixed_requests()
    seq = sequential_reference
    bat_core = make_core(model, params, grammar, prefill_chunk_tokens=16,
                         prefill_token_budget=64)
    bat = run_requests(bat_core, specs, sequential=False)

    # the packed path actually engaged (several rows per dispatch)
    m = bat_core.metrics()
    assert m["prefill_batch_occupancy"] > 1.0
    for name, *_ in specs:
        assert flat(bat[name]) == flat(seq[name]), name
        assert bat[name][-1].finish_reason == seq[name][-1].finish_reason
        assert [o.cached_tokens for o in bat[name]] == \
               [o.cached_tokens for o in seq[name]], name
    # logprob parity on the top_logprobs request (ids exact, values tight)
    lp_b, lp_s = flat(bat["lp"], "logprobs"), flat(seq["lp"], "logprobs")
    np.testing.assert_allclose(lp_b, lp_s, rtol=2e-5, atol=2e-6)
    tb = [t for o in bat["lp"] for t in (o.top_logprobs or [])]
    ts = [t for o in seq["lp"] for t in (o.top_logprobs or [])]
    assert [[i for i, _ in step] for step in tb] == \
           [[i for i, _ in step] for step in ts]
    np.testing.assert_allclose(
        [v for step in tb for _, v in step],
        [v for step in ts for _, v in step], rtol=2e-5, atol=2e-6)


def test_dispatch_count_win(setup):
    """N short prompts totalling T tokens prefill in ~ceil(T/budget)
    dispatches instead of N — the conversion the tentpole exists for."""
    model, params, _ = setup
    rng = np.random.RandomState(1)
    n = 6
    specs = [
        (f"r{i}",
         [int(x) for x in rng.randint(3, 259, size=16)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2))
        for i in range(n)
    ]  # 96 prompt tokens total

    legacy = make_core(model, params)
    run_requests(legacy, specs, sequential=False)
    assert legacy.metrics()["prefill_dispatches_total"] == n

    one = make_core(model, params, prefill_token_budget=128)
    run_requests(one, specs, sequential=False)
    assert one.metrics()["prefill_dispatches_total"] == 1  # ceil(96/128)
    assert one.metrics()["prefill_batch_occupancy"] == n

    two = make_core(model, params, prefill_token_budget=64)
    run_requests(two, specs, sequential=False)
    assert two.metrics()["prefill_dispatches_total"] == 2  # ceil(96/64)


def test_budget_splits_long_prompt(setup):
    """A single prompt larger than the budget chunks by the budget —
    ceil(len/budget) dispatches, output identical to the legacy path."""
    model, params, _ = setup
    rng = np.random.RandomState(2)
    prompt = [int(x) for x in rng.randint(3, 259, size=100)]
    specs = [("r", prompt, SamplingOptions(temperature=0.0),
              StopConditions(max_tokens=4))]

    legacy = make_core(model, params)
    ref = run_requests(legacy, specs, sequential=False)

    core = make_core(model, params, prefill_token_budget=32)
    got = run_requests(core, specs, sequential=False)
    assert flat(got["r"]) == flat(ref["r"])
    assert core.metrics()["prefill_dispatches_total"] == 4  # ceil(100/32)


def test_prefix_join_survives_batching(setup):
    """Concurrent identical prompts in the same batch still join via the
    reserve/commit protocol: the second request absorbs the first's
    committed blocks instead of packing duplicate compute into the
    ragged dispatch."""
    model, params, _ = setup
    rng = np.random.RandomState(3)
    prompt = [int(x) for x in rng.randint(3, 259, size=41)]
    specs = [
        ("a", prompt, SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=4)),
        ("b", prompt, SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=4)),
    ]
    core = make_core(model, params, prefill_token_budget=128)
    outs = run_requests(core, specs, sequential=False)
    assert flat(outs["a"]) == flat(outs["b"])
    # owner computed 41 tokens; the joiner only its uncovered tail (the
    # final partial block), never a duplicate of the 5 full blocks
    assert core.prompt_tokens_computed == 41 + (41 - 40)
    assert outs["b"][0].cached_tokens == 40


def test_budget_utilization_metric(setup):
    model, params, _ = setup
    rng = np.random.RandomState(4)
    specs = [
        ("r0", [int(x) for x in rng.randint(3, 259, size=24)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2)),
        ("r1", [int(x) for x in rng.randint(3, 259, size=8)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2)),
    ]
    core = make_core(model, params, prefill_token_budget=64)
    run_requests(core, specs, sequential=False)
    m = core.metrics()
    assert m["prefill_dispatches_total"] == 1
    assert m["prefill_budget_utilization"] == pytest.approx(32 / 64)


def test_prefill_gauges_on_http_metrics(setup):
    """The batching gauges ride /metrics next to the fault counters."""
    from dynamo_tpu.engine import counters as engine_counters
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.obs.metric_names import EngineMetric as EM

    model, params, _ = setup
    engine_counters.reset()
    rng = np.random.RandomState(5)
    specs = [
        (f"r{i}", [int(x) for x in rng.randint(3, 259, size=16)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2))
        for i in range(3)
    ]
    core = make_core(model, params, prefill_token_budget=128)
    run_requests(core, specs, sequential=False)
    text = Metrics().render()
    assert f"{EM.PREFILL_DISPATCHES_TOTAL} 1" in text
    assert f"{EM.PREFILL_TOKENS_TOTAL} 48" in text
    assert f"{EM.PREFILL_BATCH_OCCUPANCY} 3" in text
    assert f"{EM.PREFILL_BUDGET_UTILIZATION} 0.375" in text
