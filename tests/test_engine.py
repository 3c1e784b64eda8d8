"""Engine end-to-end: continuous batching on a tiny Llama, checked against
HF transformers greedy generation; prefix-cache reuse; cancellation."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import AsyncLLMEngine, EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import (
    BackendInput,
    FinishReason,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.models.loader import load_params_from_state_dict
from dynamo_tpu.runtime.engine import Context


@pytest.fixture(scope="session")
def setup():
    # session-scoped: four test modules share this build (~8s each if
    # rebuilt); everything returned is treated read-only by every user
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
    return hf, model, params


def hf_greedy(hf, prompt, n):
    import torch

    with torch.no_grad():
        out = hf.generate(
            torch.tensor([prompt]),
            max_new_tokens=n,
            do_sample=False,
            pad_token_id=0,
            eos_token_id=None,  # our engine has no EOS configured in these tests
        )
    return out[0][len(prompt) :].tolist()


def make_core(model, params, **kw):
    cfg = EngineConfig(
        max_batch_size=4,
        max_model_len=128,
        block_size=8,
        num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
        **kw,
    )
    return EngineCore(model, params, cfg)


def collect_greedy(core, prompt, n, request_id="r1"):
    outs = []
    req = EngineRequest(
        request_id=request_id,
        prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=n),
        emit=outs.append,
    )
    core.submit(req)
    for _ in range(n + 20):
        if not core.step():
            break
    toks = [t for o in outs for t in o.token_ids]
    return toks, outs, req


def test_greedy_matches_hf(setup):
    hf, model, params = setup
    prompt = list(np.random.RandomState(0).randint(1, 128, size=13))
    expect = hf_greedy(hf, prompt, 10)
    core = make_core(model, params)
    got, outs, _ = collect_greedy(core, prompt, 10)
    assert got == expect
    assert outs[-1].finish_reason == FinishReason.LENGTH


def test_continuous_batching_two_requests(setup):
    hf, model, params = setup
    rng = np.random.RandomState(1)
    p1 = list(rng.randint(1, 128, size=9))
    p2 = list(rng.randint(1, 128, size=21))
    e1, e2 = hf_greedy(hf, p1, 8), hf_greedy(hf, p2, 8)

    core = make_core(model, params)
    outs1, outs2 = [], []
    core.submit(
        EngineRequest("a", p1, SamplingOptions(temperature=0.0),
                      StopConditions(max_tokens=8), outs1.append)
    )
    core.submit(
        EngineRequest("b", p2, SamplingOptions(temperature=0.0),
                      StopConditions(max_tokens=8), outs2.append)
    )
    while core.step():
        pass
    assert [t for o in outs1 for t in o.token_ids] == e1
    assert [t for o in outs2 for t in o.token_ids] == e2


def test_prefix_reuse_speeds_second_request(setup):
    hf, model, params = setup
    prompt = list(np.random.RandomState(2).randint(1, 128, size=33))
    core = make_core(model, params)
    got1, outs1, _ = collect_greedy(core, prompt, 6, "r1")
    got2, outs2, _ = collect_greedy(core, prompt, 6, "r2")
    assert got1 == got2
    assert outs1[0].cached_tokens == 0
    # 33 tokens = 4 full blocks + 1; all 4 committed after prefill
    assert outs2[0].cached_tokens == 32


def test_eos_and_stop_tokens(setup):
    hf, model, params = setup
    prompt = list(np.random.RandomState(3).randint(1, 128, size=8))
    core = make_core(model, params)
    expect = hf_greedy(hf, prompt, 8)
    # make the 3rd expected token a stop token
    outs = []
    core.submit(
        EngineRequest("s", prompt, SamplingOptions(temperature=0.0),
                      StopConditions(max_tokens=20, stop_token_ids=[expect[2]]),
                      outs.append)
    )
    while core.step():
        pass
    toks = [t for o in outs for t in o.token_ids]
    assert toks == expect[:3]
    assert outs[-1].finish_reason == FinishReason.STOP


def test_async_engine_and_cancellation(setup):
    _, model, params = setup

    async def go():
        core = make_core(model, params)
        eng = AsyncLLMEngine(core).start()
        try:
            # full generation
            ctx = Context(
                BackendInput(token_ids=[5, 6, 7],
                             sampling=SamplingOptions(temperature=0.0),
                             stops=StopConditions(max_tokens=5))
            )
            outs = [o async for o in eng.generate(ctx)]
            assert sum(len(o.token_ids) for o in outs) == 5
            assert outs[-1].finished

            # cancellation mid-stream
            ctx2 = Context(
                BackendInput(token_ids=[5, 6, 7],
                             sampling=SamplingOptions(temperature=0.0),
                             stops=StopConditions(max_tokens=500))
            )
            got = []
            async for o in eng.generate(ctx2):
                got.append(o)
                if len(got) == 3:
                    ctx2.stop_generating()
            assert got[-1].finish_reason == FinishReason.CANCELLED
            # pool fully reclaimed after both requests
            assert core.block_manager.active_blocks == 0
        finally:
            eng.shutdown()

    asyncio.new_event_loop().run_until_complete(go())


def test_engine_thread_freezes_what_a_build_leaves(setup):
    """A step that built its program moves the heap out of the collector's
    sight (ROADMAP S11); a step that ran a built one does not, and a
    stopped engine gives it back."""
    import gc

    from dynamo_tpu.engine import async_engine

    _, model, params = setup
    calls = []
    real = async_engine.settle_heap

    def counted():
        real()
        calls.append(gc.get_freeze_count())

    async def ask(eng, n):
        ctx = Context(BackendInput(token_ids=list(range(3, 3 + n)),
                                   sampling=SamplingOptions(temperature=0.0),
                                   stops=StopConditions(max_tokens=4)))
        return [o async for o in eng.generate(ctx)]

    async def go():
        # a batch width no other test of this process has built
        cfg = EngineConfig(max_batch_size=3, max_model_len=128, block_size=8,
                           num_blocks=64, prefill_buckets=[16, 32, 64, 128])
        eng = AsyncLLMEngine(EngineCore(model, params, cfg)).start()
        try:
            await ask(eng, 5)
            built = len(calls)
            assert built >= 1 and calls[-1] > 0
            await ask(eng, 5)  # the same shapes: nothing to build
            assert len(calls) == built
        finally:
            eng.shutdown()

    async_engine.settle_heap = counted
    try:
        asyncio.new_event_loop().run_until_complete(go())
    finally:
        async_engine.settle_heap = real
    assert gc.get_freeze_count() == 0


def test_sampling_with_temperature_runs(setup):
    _, model, params = setup
    core = make_core(model, params)
    outs = []
    core.submit(
        EngineRequest("t", [1, 2, 3], SamplingOptions(temperature=0.8, top_k=10, top_p=0.9),
                      StopConditions(max_tokens=10), outs.append)
    )
    while core.step():
        pass
    toks = [t for o in outs for t in o.token_ids]
    assert len(toks) == 10
    assert all(0 <= t < 128 for t in toks)


def test_chunked_prefill_matches_unchunked(setup):
    """Greedy output is identical whether the prompt prefills in one step
    or in block-aligned chunks (chunked prefill, VERDICT r1 #2)."""
    hf, model, params = setup
    prompt = list(np.random.RandomState(7).randint(1, 128, size=50))
    expect = hf_greedy(hf, prompt, 6)

    core = make_core(model, params, prefill_chunk_tokens=16)
    got, outs, _ = collect_greedy(core, prompt, 6)
    assert got == expect
    # 50 tokens / 16-token chunks -> 4 prefill dispatches (16+16+16+2)
    assert core.prefill_steps == 4


def test_chunked_prefill_interleaves_decode(setup):
    """While a long prompt prefills in chunks, already-running requests
    keep decoding between chunks — decode never stalls for the whole
    prompt (bounded ITL)."""
    hf, model, params = setup
    rng = np.random.RandomState(8)
    short = list(rng.randint(1, 128, size=5))
    long = list(rng.randint(1, 128, size=64))
    e_short = hf_greedy(hf, short, 12)
    e_long = hf_greedy(hf, long, 4)

    core = make_core(model, params, prefill_chunk_tokens=16)
    outs_s, outs_l = [], []
    core.submit(EngineRequest("s", short, SamplingOptions(temperature=0.0),
                              StopConditions(max_tokens=12), outs_s.append))
    # let the short request prefill and start decoding
    core.step()
    assert core.prefill_steps == 1
    core.submit(EngineRequest("l", long, SamplingOptions(temperature=0.0),
                              StopConditions(max_tokens=4), outs_l.append))

    # record the phase of each scheduling iteration
    phases = []
    while core.step():
        phases.append((core.prefill_steps, core.decode_steps))
    assert [t for o in outs_s for t in o.token_ids] == e_short
    assert [t for o in outs_l for t in o.token_ids] == e_long

    # the long prompt took 4 chunks (64/16); decode steps advanced between
    # consecutive prefill chunks (interleaving, not a prefill stall)
    prefill_iters = [i for i, (p, d) in enumerate(phases)
                     if p > (phases[i - 1][0] if i else 1)]
    assert len(prefill_iters) == 4
    for a, b in zip(prefill_iters, prefill_iters[1:]):
        assert any(phases[i][1] > phases[a][1] for i in range(a + 1, b + 1)), \
            f"no decode progress between prefill chunks at iters {a}..{b}"


def test_prefill_is_served_in_order_of_admission(setup):
    """A request admitted later into a LOWER slot does not cut in ahead of
    a long prompt that is mid-prefill in a higher one: chunks go to the
    request admitted first until its prompt is done."""
    hf, model, params = setup
    rng = np.random.RandomState(9)
    first = []

    def req(rid, n, max_tokens):
        return EngineRequest(
            rid, list(rng.randint(1, 128, size=n)),
            SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=max_tokens),
            lambda out, rid=rid: first.append(rid) if rid not in first else None)

    core = make_core(model, params, prefill_chunk_tokens=16)
    a, long = req("a", 5, 1), req("long", 64, 2)
    core.submit(a)
    core.submit(long)
    core.step()                 # admits both; prefills a, which then ends
    assert first == ["a"] and core.slots[0] is None and long.slot == 1
    b = req("b", 32, 2)
    core.submit(b)
    while core.step():
        pass
    assert b.slot == 0 < long.slot          # the premise: b sits lower
    assert first == ["a", "long", "b"]


def test_logprobs_and_penalties_through_engine(setup):
    """Engine emits per-token logprobs + top_logprobs when requested, and
    frequency penalties actually change what gets sampled (previously dead
    fields, VERDICT r1 weak #3)."""
    hf, model, params = setup
    prompt = list(np.random.RandomState(9).randint(1, 128, size=12))

    core = make_core(model, params)
    outs = []
    core.submit(EngineRequest(
        "lp", list(prompt),
        SamplingOptions(temperature=0.0, logprobs=True, top_logprobs=3),
        StopConditions(max_tokens=5), outs.append,
    ))
    while core.step():
        pass
    toks = [t for o in outs for t in o.token_ids]
    lps = [l for o in outs if o.logprobs for l in o.logprobs]
    tops = [t for o in outs if o.top_logprobs for t in o.top_logprobs]
    assert len(lps) == len(toks) == 5
    assert all(l <= 0.0 for l in lps)
    for tok, lp, top in zip(toks, lps, tops):
        assert len(top) == 3
        # greedy: the chosen token IS the best candidate
        assert top[0][0] == tok
        assert np.isclose(top[0][1], lp, atol=1e-5)
        # candidates sorted descending
        assert top[0][1] >= top[1][1] >= top[2][1]

    # greedy + overwhelming frequency penalty => no token repeats
    core2 = make_core(model, params)
    outs2 = []
    core2.submit(EngineRequest(
        "pen", list(prompt),
        SamplingOptions(temperature=0.0, frequency_penalty=2.0),
        StopConditions(max_tokens=12), outs2.append,
    ))
    while core2.step():
        pass
    toks2 = [t for o in outs2 for t in o.token_ids]
    assert len(toks2) == 12
    # tiny random model greedily repeats without the penalty; with a 2.0
    # frequency penalty every repeat costs 2.0 logits per occurrence, so
    # runs of identical tokens must be broken up
    max_run = max(
        len(list(g)) for _, g in __import__("itertools").groupby(toks2)
    )
    assert max_run <= 2


# ------------------------------------------- where a decode step's row ends
# The three kinds of row the one decode step treats differently: a plain
# row's token is carried on the device, so the next decode goes out before
# the host has read it; a penalty buffer and a grammar state are the host's
# to build from the token, so a batch with such a row is read back first.
ROW_KINDS = {
    "plain": SamplingOptions(temperature=0.0),
    "penalties": SamplingOptions(temperature=0.0, frequency_penalty=0.5),
    "json": SamplingOptions(temperature=0.0, json_mode=True),
}
EOS = 0


@pytest.fixture(scope="module")
def ascii_grammar():
    """JSON mode over the toy's 128 tokens: token i is the byte i."""
    from dynamo_tpu.engine.grammar import JsonGrammar

    return JsonGrammar.from_token_bytes(
        [None] + [bytes([i]) for i in range(1, 128)], eos_ids=[EOS])


def serve_one(model, params, grammar, sampling, stops, seed, **cfg):
    """One request through an engine of its own; returns (core, tokens,
    the last output's finish reason)."""
    cfg = EngineConfig(**{**dict(max_batch_size=1, max_model_len=128,
                                 block_size=8, num_blocks=64,
                                 prefill_buckets=[16]), **cfg})
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS],
                      grammar=grammar)
    outs = []
    prompt = list(np.random.RandomState(seed).randint(1, 128, size=10))
    core.submit(EngineRequest("x", prompt, sampling, stops, outs.append))
    while core.step():
        pass
    assert all(s is None for s in core.slots)
    return (core, [t for o in outs for t in o.token_ids],
            outs[-1].finish_reason)


@pytest.mark.parametrize("kind", sorted(ROW_KINDS))
def test_a_stop_token_ends_the_row_at_the_token(setup, ascii_grammar, kind):
    """A stop the host cannot foresee ends the stream AT its token.  A
    plain row finds it one dispatch late (the next decode went out with
    the token carried on the device) and throws that one sample away; the
    other two kinds are read back before the next decode is built."""
    _, model, params = setup
    sampling = ROW_KINDS[kind]
    _, ref, _ = serve_one(model, params, ascii_grammar, sampling,
                          StopConditions(max_tokens=6, ignore_eos=True), 23)
    # the first token past the first that the stream has not shown before
    k = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    core, toks, reason = serve_one(
        model, params, ascii_grammar, sampling,
        StopConditions(max_tokens=20, stop_token_ids=[ref[k]],
                       ignore_eos=True), 23)
    assert toks == ref[:k + 1]
    assert reason == FinishReason.STOP
    m = core.metrics()
    carried = kind == "plain"
    assert m["ahead_discards_total"] == (1 if carried else 0)
    assert (m["ahead_dispatches_total"] > 0) == carried
    assert core.block_manager.active_blocks == 0


@pytest.mark.parametrize("kind", sorted(ROW_KINDS))
def test_block_exhaustion_finishes_at_length(setup, ascii_grammar, kind):
    """3 blocks of 8: at most 24 tokens of one sequence have K/V."""
    _, model, params = setup
    core, toks, reason = serve_one(
        model, params, ascii_grammar, ROW_KINDS[kind],
        StopConditions(max_tokens=100, ignore_eos=True), 24, num_blocks=3)
    assert reason == FinishReason.LENGTH
    # 24 block-resident tokens + the last sample (whose K/V is never needed)
    assert len(toks) == 24 - 10 + 1
    assert core.block_manager.free_blocks == 3  # everything released
    assert core.metrics()["ahead_discards_total"] == 0


@pytest.mark.parametrize("kind", sorted(ROW_KINDS))
def test_decode_respects_max_model_len(setup, ascii_grammar, kind):
    """The host foresees this end: the row is left out of the decode
    behind the one that reaches the limit, and no sample is wasted."""
    _, model, params = setup
    core, toks, reason = serve_one(
        model, params, ascii_grammar, ROW_KINDS[kind],
        StopConditions(max_tokens=100, ignore_eos=True), 25,
        max_model_len=16, num_blocks=8)
    assert reason == FinishReason.LENGTH
    assert len(toks) == 16 - 10  # total tokens capped at max_model_len
    assert core.metrics()["ahead_discards_total"] == 0
    assert core.decode_steps == len(toks) - 1
