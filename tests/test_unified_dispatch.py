"""Unified mixed prefill+decode dispatch: one token-budget ragged step
per mixed turn must be invisible to callers — seeded-stream parity
against the legacy prefill-then-decode paths (tokens, logprobs,
cached_tokens, grammar, seeds, joins, aborts), the 2-dispatches-to-1
win per mixed turn, and the mixed-kernel CPU oracle."""

import jax
import jax.numpy as jnp
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


def flat(outs, field="token_ids"):
    return [x for o in outs for x in (getattr(o, field) or [])]


def mixed_specs():
    """Deterministic-stream mix: every request is greedy or seeded, so
    both schedulers must produce token-identical streams regardless of
    dispatch composition.  Covers a long prompt that stays mid-chunk
    across turns, grammar-constrained decoding, seeded sampling with
    top_logprobs, penalties, and a plain greedy request."""
    rng = np.random.RandomState(42)
    p = lambda n: [int(x) for x in rng.randint(3, 259, size=n)]
    return [
        ("long", p(44), SamplingOptions(temperature=1.0, seed=7),
         StopConditions(max_tokens=5)),
        ("json", p(8), SamplingOptions(temperature=0.0, json_mode=True),
         StopConditions(max_tokens=8)),
        ("lp", p(10),
         SamplingOptions(temperature=0.9, seed=123, logprobs=True,
                         top_logprobs=3),
         StopConditions(max_tokens=5)),
        ("pen", p(12),
         SamplingOptions(temperature=0.0, frequency_penalty=0.7,
                         presence_penalty=0.3),
         StopConditions(max_tokens=5)),
        ("plain", p(9), SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=5)),
    ]


def run_staggered(core, specs, head=2, stagger=4):
    """Submit ``head`` requests, run a few turns so they reach decode,
    then submit the rest — forcing turns where both phases have work."""
    outs = {name: [] for name, *_ in specs}
    reqs = [
        EngineRequest(name, list(prompt), sampling, stops,
                      emit=outs[name].append)
        for name, prompt, sampling, stops in specs
    ]
    for r in reqs[:head]:
        core.submit(r)
    for _ in range(stagger):
        core.step()
    for r in reqs[head:]:
        core.submit(r)
    drain(core)
    return outs


def assert_stream_parity(specs, ref, got, names=None):
    for name in (names or [n for n, *_ in specs]):
        assert flat(got[name]) == flat(ref[name]), name
        assert got[name][-1].finish_reason == ref[name][-1].finish_reason
        assert [o.cached_tokens for o in got[name]] == \
               [o.cached_tokens for o in ref[name]], name


def test_mixed_workload_parity(setup):
    """The tentpole gate: mixed prefill+decode turns collapsed into one
    unified dispatch produce token-identical output streams vs the
    legacy alternating interleave — incl. grammar-constrained, seeded,
    penalised and top_logprobs requests."""
    model, params, grammar = setup
    specs = mixed_specs()
    legacy = make_core(model, params, grammar, prefill_chunk_tokens=16,
                       prefill_token_budget=64)
    ref = run_staggered(legacy, specs)
    assert legacy.counts.unified_dispatches_total == 0

    uni_core = make_core(model, params, grammar, prefill_chunk_tokens=16,
                         prefill_token_budget=64,
                         unified_token_dispatch=True)
    uni = run_staggered(uni_core, specs)
    # the mixed path actually engaged, and each engagement packed decode
    # rows AND prefill tokens onto one axis
    assert uni_core.counts.unified_dispatches_total > 0
    assert uni_core.counts.unified_decode_rows > 0
    assert uni_core.counts.unified_prefill_tokens > 0

    assert_stream_parity(specs, ref, uni)
    # logprob parity on the top_logprobs request (ids exact, values tight)
    lp_u, lp_r = flat(uni["lp"], "logprobs"), flat(ref["lp"], "logprobs")
    np.testing.assert_allclose(lp_u, lp_r, rtol=2e-5, atol=2e-6)
    tu = [t for o in uni["lp"] for t in (o.top_logprobs or [])]
    tr = [t for o in ref["lp"] for t in (o.top_logprobs or [])]
    assert [[i for i, _ in step] for step in tu] == \
           [[i for i, _ in step] for step in tr]
    np.testing.assert_allclose(
        [v for step in tu for _, v in step],
        [v for step in tr for _, v in step], rtol=2e-5, atol=2e-6)


def test_prefill_only_and_decode_only_parity(setup):
    """Pure workloads keep their legacy dispatches under the flag and
    stay token-identical: a prefill burst (all prompts at once, 1 token
    each) and a lone decoder (no arrivals while it runs)."""
    model, params, _ = setup
    rng = np.random.RandomState(1)
    prefill_specs = [
        (f"r{i}", [int(x) for x in rng.randint(3, 259, size=16)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=1))
        for i in range(4)
    ]
    decode_specs = [
        ("d", [int(x) for x in rng.randint(3, 259, size=10)],
         SamplingOptions(temperature=1.0, seed=11),
         StopConditions(max_tokens=12)),
    ]
    for specs in (prefill_specs, decode_specs):
        legacy = make_core(model, params, prefill_token_budget=64)
        ref = run_staggered(legacy, specs, head=len(specs), stagger=0)
        uni_core = make_core(model, params, prefill_token_budget=64,
                             unified_token_dispatch=True)
        got = run_staggered(uni_core, specs, head=len(specs), stagger=0)
        assert_stream_parity(specs, ref, got)
        # no mixed turns existed, so the unified impl never dispatched
        assert uni_core.counts.unified_dispatches_total == 0
        assert uni_core._unified_fn._cache_size() == 0


def test_mixed_turn_is_one_dispatch(setup):
    """THE dispatch-count win, turn by turn: with one request decoding
    and one mid-prefill, a unified step() issues exactly ONE jitted call
    that advances BOTH — where the legacy interleave needs two."""
    model, params, _ = setup
    rng = np.random.RandomState(2)
    deco = EngineRequest(
        "deco", [int(x) for x in rng.randint(3, 259, size=8)],
        SamplingOptions(temperature=0.0), StopConditions(max_tokens=40),
        emit=lambda o: None)
    long_prompt = [int(x) for x in rng.randint(3, 259, size=48)]

    core = make_core(model, params, prefill_chunk_tokens=16,
                     prefill_token_budget=64,
                     unified_token_dispatch=True)
    core.submit(deco)
    for _ in range(3):
        core.step()  # deco is now decoding
    pref = EngineRequest("pref", long_prompt, SamplingOptions(temperature=0.0),
                         StopConditions(max_tokens=1), emit=lambda o: None)
    core.submit(pref)
    core.step()  # admission + first mixed turn
    while pref.computed_tokens < pref.prompt_len:
        gen_before = deco.generated
        computed_before = pref.computed_tokens
        steps_before = core.steps
        core.step()
        assert core.steps == steps_before + 1          # ONE jitted call
        assert deco.generated == gen_before + 1        # decode advanced
        assert pref.computed_tokens > computed_before  # prefill advanced
    assert core.counts.unified_dispatches_total >= 3  # 48 tokens / 16-token chunks

    # the legacy interleave pays 2 dispatches per (chunk, burst) pair on
    # the identical scenario — strictly more total dispatches
    legacy = make_core(model, params, prefill_chunk_tokens=16,
                       prefill_token_budget=64)
    deco2 = EngineRequest("deco", list(deco.prompt),
                          SamplingOptions(temperature=0.0),
                          StopConditions(max_tokens=40), emit=lambda o: None)
    legacy.submit(deco2)
    for _ in range(3):
        legacy.step()
    pref2 = EngineRequest("pref", list(long_prompt),
                          SamplingOptions(temperature=0.0),
                          StopConditions(max_tokens=1), emit=lambda o: None)
    legacy.submit(pref2)
    steps0 = legacy.steps
    while pref2.computed_tokens < pref2.prompt_len:
        legacy.step()
    assert legacy.steps - steps0 > core.counts.unified_dispatches_total


def test_join_under_batching_unified(setup):
    """Prefix-join reserve/commit carries over: identical prompts
    submitted while another request decodes still join — the second
    absorbs committed blocks instead of packing duplicate compute into
    the unified dispatch."""
    model, params, _ = setup
    rng = np.random.RandomState(3)
    prompt = [int(x) for x in rng.randint(3, 259, size=41)]
    specs = [
        ("deco", [int(x) for x in rng.randint(3, 259, size=8)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=20)),
        ("a", prompt, SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=4)),
        ("b", prompt, SamplingOptions(temperature=0.0),
         StopConditions(max_tokens=4)),
    ]
    core = make_core(model, params, prefill_token_budget=128,
                     unified_token_dispatch=True)
    outs = run_staggered(core, specs, head=1, stagger=3)
    assert core.counts.unified_dispatches_total > 0
    assert flat(outs["a"]) == flat(outs["b"])
    # owner computed 41 tokens; the joiner only its uncovered tail (the
    # final partial block) — plus the decoy's 8-token prompt
    assert core.prompt_tokens_computed == 8 + 41 + (41 - 40)
    assert outs["b"][0].cached_tokens == 40


def test_mid_batch_abort_of_prefill_row(setup):
    """Aborting a mid-chunk prefill request between unified turns
    cancels it cleanly; the decoding request and a second prompt are
    unaffected (same stream as a run where the victim never existed)."""
    model, params, _ = setup
    rng = np.random.RandomState(4)
    deco_prompt = [int(x) for x in rng.randint(3, 259, size=8)]
    victim_prompt = [int(x) for x in rng.randint(3, 259, size=48)]
    other_prompt = [int(x) for x in rng.randint(3, 259, size=12)]

    def run(abort_victim):
        core = make_core(model, params, prefill_chunk_tokens=16,
                         prefill_token_budget=32,
                         unified_token_dispatch=True)
        outs = {"deco": [], "victim": [], "other": []}
        core.submit(EngineRequest(
            "deco", list(deco_prompt), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=12), emit=outs["deco"].append))
        for _ in range(3):
            core.step()
        core.submit(EngineRequest(
            "victim", list(victim_prompt), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=4), emit=outs["victim"].append))
        core.submit(EngineRequest(
            "other", list(other_prompt), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=4), emit=outs["other"].append))
        core.step()  # first mixed turn: victim is now mid-chunk
        if abort_victim:
            core.abort("victim")
        drain(core)
        return core, outs

    core, outs = run(abort_victim=True)
    assert core.counts.unified_dispatches_total > 0
    from dynamo_tpu.llm.protocols import FinishReason

    assert outs["victim"][-1].finish_reason == FinishReason.CANCELLED
    _, ref = run(abort_victim=False)
    assert flat(outs["deco"]) == flat(ref["deco"])
    assert flat(outs["other"]) == flat(ref["other"])


def test_unified_int8_cache_parity(setup):
    """The unified write path splits row-scatter and block-granular
    regions for the QuantKvCache too (data AND scale pools): greedy
    streams match the legacy int8 paths token for token."""
    model, params, _ = setup
    rng = np.random.RandomState(5)
    specs = [
        ("deco", [int(x) for x in rng.randint(3, 259, size=9)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=6)),
        ("p1", [int(x) for x in rng.randint(3, 259, size=20)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=3)),
    ]
    legacy = make_core(model, params, prefill_chunk_tokens=16,
                       prefill_token_budget=64, cache_dtype="int8")
    ref = run_staggered(legacy, specs, head=1, stagger=3)
    uni_core = make_core(model, params, prefill_chunk_tokens=16,
                         prefill_token_budget=64, cache_dtype="int8",
                         unified_token_dispatch=True)
    got = run_staggered(uni_core, specs, head=1, stagger=3)
    assert uni_core.counts.unified_dispatches_total > 0
    assert_stream_parity(specs, ref, got)


def test_mixed_kernel_cpu_oracle():
    """CPU oracle for the mixed-chunk kernel (ROADMAP standing note:
    hardware down, every new hot path needs a CPU oracle): the Pallas
    ragged kernel in interpret mode matches ragged_prefill_attention on
    a flat axis holding decode rows — 1 fresh token each, starts NOT
    block-aligned, full cached prefix — ahead of a prefill chunk span
    with its own cached prefix."""
    from dynamo_tpu.ops.paged_attention import ragged_prefill_attention
    from dynamo_tpu.ops.pallas.prefill_attention import (
        ragged_paged_prefill_attention,
    )

    rng = np.random.default_rng(7)
    h, hk, d, bs, n, m = 4, 2, 32, 16, 32, 8
    t = 64           # flat axis: 16-slot decode region + 48-token span
    d_region = 16
    cache = jnp.asarray(
        rng.normal(size=(2, n, 2, bs, hk * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
    ids = rng.permutation(n).astype(np.int32)
    bt = jnp.asarray(np.resize(ids, (4, m)))
    # rows 0-2: decode rows with mid-block starts (33, 1, 17); row 3: a
    # 48-token prefill chunk resuming at block-aligned start 32
    starts = jnp.asarray([33, 1, 17, 32], jnp.int32)
    seq_lens = jnp.asarray([34, 2, 18, 80], jnp.int32)
    roff = jnp.asarray([0, 1, 2, d_region], jnp.int32)
    seq_ids = np.full((1, t), -1, np.int32)
    seq_ids[0, :3] = [0, 1, 2]
    seq_ids[0, d_region:] = 3
    seq_ids = jnp.asarray(seq_ids)
    pb = 4  # covers ceil(33/16)=3 decode prefix blocks and 32/16=2

    ref = ragged_prefill_attention(
        q, k, v, cache, jnp.int32(1), bt, seq_lens, starts, roff,
        seq_ids, pb)
    out = ragged_paged_prefill_attention(
        q, k, v, cache, jnp.int32(1), bt, seq_lens, starts, roff,
        rows_per_chunk=32, blocks_per_chunk=2, interpret=True)
    # compare only real rows' tokens (padding slots are garbage by
    # contract on both paths)
    real = np.asarray(seq_ids[0]) >= 0
    np.testing.assert_allclose(
        np.asarray(out)[0][real], np.asarray(ref)[0][real],
        rtol=2e-5, atol=2e-5)


def test_unified_gauges_on_http_metrics(setup):
    """The unified counters ride /metrics next to the prefill gauges."""
    from dynamo_tpu.engine import counters as engine_counters
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.obs.metric_names import EngineMetric as EM

    model, params, _ = setup
    engine_counters.reset()
    rng = np.random.RandomState(6)
    specs = [
        ("deco", [int(x) for x in rng.randint(3, 259, size=8)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=10)),
        ("p1", [int(x) for x in rng.randint(3, 259, size=16)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2)),
    ]
    core = make_core(model, params, prefill_token_budget=32,
                     unified_token_dispatch=True)
    run_staggered(core, specs, head=1, stagger=3)
    assert core.counts.unified_dispatches_total > 0
    text = Metrics().render()
    assert (f"{EM.UNIFIED_DISPATCHES_TOTAL} "
            f"{core.counts.unified_dispatches_total}") in text
    assert (f"{EM.UNIFIED_DECODE_ROWS_TOTAL} "
            f"{core.counts.unified_decode_rows}") in text
    assert (f"{EM.UNIFIED_PREFILL_TOKENS_TOTAL} "
            f"{core.counts.unified_prefill_tokens}") in text
    assert f"{EM.UNIFIED_BUDGET_UTILIZATION} " in text
