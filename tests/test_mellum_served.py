"""The Mellum toy (mellum_tiny.TINY) through ``EngineCore``'s default path:
chunked prefill and decode against the reference past the window, a question
on a cached document against the same prompt asked cold, a request that waits
for another's blocks, the window's two counters and gauges, the start-up line
- and that a model of one kind of layer counts and says what it did."""

import numpy as np
import pytest

from hybrid_linear_tiny import drain, submit, worst_delta as _worst_delta
from mellum_tiny import (ROUNDING, TINY, WINDOW, build, engine, tokens_of,
                         want)


def worst_delta(params, prompt, answer) -> float:
    return _worst_delta(params, prompt, answer, cfg=TINY, want=want)


def test_engine_serves_it_in_chunks_then_decodes_against_the_reference():
    """Two requests, one of three chunks (75 tokens, chunk 32; the window is
    24): every generated position's top log-probabilities against the
    reference's full forward, and what the engine counts of the window."""
    model, params = build()
    core = engine(model, params)
    long, short = tokens_of(75, 1), tokens_of(20, 2)
    got: dict = {}
    submit(core, "long", long, 6, got)
    submit(core, "short", short, 10, got)
    drain(core)
    assert len(got["long"][0]) == 6 and len(got["short"][0]) == 10
    assert worst_delta(params, long, got["long"]) < ROUNDING
    assert worst_delta(params, short, got["short"]) < ROUNDING
    m = core.metrics()
    assert (m["window_layers"], m["sliding_window"], m["cache_layers"]) == (
        6, WINDOW, 8)
    assert m["prefill_dispatches_total"] == 3 + 1
    # the decode rows' blocks, six window layers: all of them while a row is
    # inside the window, 24 / 8 + 1 of ~10 past it
    walked = m["decode_kv_window_blocks_walked_total"]
    span = m["decode_kv_window_blocks_span_total"]
    assert span == 6 * m["decode_kv_blocks_walked_total"]
    assert 0 < walked < span
    line = core.attention_impls()
    assert set(line) == {"decode", "mq", "prefill", "ragged"}
    assert all(why.endswith("6 window layers of 24, 2 full")
               for _, why in line.values())


def test_the_windows_counters_follow_the_kernels_walk():
    """Rows of 5, 24, 25, 40 and 100 tokens, an empty slot: a window layer's
    walk is blocks floor(max(len - 24, 0) / 8) .. ceil(len / 8)."""
    model, params = build()
    core = engine(model, params)
    lens = np.asarray([5, 24, 25, 40, 100, 0, 0, 0], np.int32)
    core._count_decode_blocks(lens[:4])
    core._count_decode_blocks(lens[4:])
    m = core.metrics()
    #        own blocks   first block of the band
    # 5   ->  1           0      -> 1
    # 24  ->  3           0      -> 3
    # 25  ->  4           0      -> 4   (position 1 is in block 0)
    # 40  ->  5           2      -> 3
    # 100 ->  13          9      -> 4   (position 76 is in block 9)
    assert m["decode_kv_blocks_walked_total"] == 1 + 3 + 4 + 5 + 13
    assert m["decode_kv_window_blocks_span_total"] == 6 * 26
    assert m["decode_kv_window_blocks_walked_total"] == 6 * (1 + 3 + 4 + 3 + 4)


def test_a_question_on_a_cached_document_is_the_same_prompt_asked_cold():
    """A 64-token document and a question: asked cold on one engine, and as
    a prefix hit (the document's blocks cached by an earlier question) on
    another.  The hit computes the question alone, over K/V the earlier
    request wrote - the window of its first queries begins inside them - and
    gives the cold prompt's log-probabilities."""
    model, params = build()
    doc, q1, q2 = tokens_of(64, 3), tokens_of(11, 4), tokens_of(13, 5)
    cold, warm = engine(model, params), engine(model, params)
    a: dict = {}
    b: dict = {}
    submit(cold, "cold", doc + q2, 8, a)
    drain(cold)
    submit(warm, "first", doc + q1, 4, b)
    drain(warm)
    before = warm.prompt_tokens_computed
    submit(warm, "hit", doc + q2, 8, b)
    drain(warm)
    assert warm.prompt_tokens_computed - before == len(q2)   # the question
    assert warm.metrics()["prefix_reuse"] == 1
    assert a["cold"][0] == b["hit"][0]
    cold_lp = [dict(c) for c in a["cold"][1]]
    for pos, cands in enumerate(b["hit"][1]):
        for tid, lp in cands:
            assert abs(lp - cold_lp[pos][tid]) < ROUNDING
    assert worst_delta(params, doc + q2, b["hit"]) < ROUNDING


def test_a_request_that_waits_for_blocks_answers_over_what_the_first_left():
    """A pool too small for two requests at once (the engine has no
    preemption: a request that finds no blocks waits for them): the second
    is admitted when the first is done, takes the blocks it freed - stale
    K/V of another sequence before and behind its own rows - and answers as
    on a roomy engine, past the window; neither is cut short."""
    model, params = build()
    p1, p2 = tokens_of(52, 6), tokens_of(50, 7)
    roomy, tight = engine(model, params), engine(model, params, num_blocks=12)
    want_: dict = {}
    got: dict = {}
    for core, out in ((roomy, want_), (tight, got)):
        submit(core, "one", p1, 24, out)
        submit(core, "two", p2, 24, out)
        drain(core)
    assert tight.metrics()["requests_cut_short_total"] == 0
    assert roomy.metrics()["decode_dispatches_total"] < tight.metrics()[
        "decode_dispatches_total"]          # one after the other
    for name, prompt in (("one", p1), ("two", p2)):
        assert len(got[name][0]) == 24 and got[name][0] == want_[name][0]
        assert worst_delta(params, prompt, got[name]) < ROUNDING


def test_a_model_of_one_kind_of_layer_counts_no_window_and_says_nothing_new():
    """A uniform full-attention toy: the window's counters stay 0, the
    gauges read 0, the start-up line is the line it was."""
    import jax

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    model = LlamaModel(ModelConfig.tiny())
    core = engine(model, model.init_params(jax.random.PRNGKey(0)))
    got: dict = {}
    submit(core, "r", tokens_of(40, 8), 6, got)
    drain(core)
    m = core.metrics()
    assert m["window_layers"] == 0 and m["sliding_window"] == 0
    assert m["decode_kv_window_blocks_walked_total"] == 0
    assert m["decode_kv_window_blocks_span_total"] == 0
    assert m["decode_kv_blocks_walked_total"] > 0
    assert {why for _, why in core.attention_impls().values()} == {
        "backend is cpu"}


@pytest.mark.parametrize("window,max_len,says", [
    (16, 128, "2 window layers of 16, 0 full"),   # a uniform window model
    (256, 128, None),        # the context cannot pass it: full is exact
])
def test_a_uniform_window_is_named_only_where_a_context_can_pass_it(
        window, max_len, says):
    import jax

    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    model = LlamaModel(ModelConfig.tiny(sliding_window=window))
    core = engine(model, model.init_params(jax.random.PRNGKey(0)),
                  max_model_len=max_len)
    whys = {why for _, why in core.attention_impls().values()}
    assert whys == {f"backend is cpu; {says}" if says else "backend is cpu"}
    assert core.metrics()["window_layers"] == 2
