"""``LlamaModel`` with ``layer_types`` (mellum_tiny.TINY: two periods of three
window layers and a full one, a rope a kind) against the benchmark's plain
reference (cellbench/reference/mellum_swa_moe.py) by direct calls of
``forward``: prefill in chunks with the band's older edge inside a chunk and
inside the cached prefix, then decode across the edge (log-probabilities, not
tokens); every negative control of scripts/mellum_longctx_check.py; the
ragged layout; and that a stack of one kind traces what it traced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.llama import LlamaModel
from mellum_tiny import (BS, CONTROLS, KV_ROUND, NB, ROUNDING, TINY, WIDTH,
                         WINDOW, build, chunk, decode, logp, tokens_of, want)


def served(model, params, toks, other,
           chunks=((0, 32, None), (32, 64, None), (64, 75, 16))):
    """80 tokens: 75 in ``chunks`` (the last padded) behind a 20-token
    sequence in slot 0, then five decode steps beside it: every position's
    log-probabilities."""
    cache = model.init_kv_cache(NB, BS)
    _, cache = chunk(model, params, cache, other, 0, 20, 32)
    got = []
    for a, b, pad in chunks:
        lp, cache = chunk(model, params, cache, toks, a, b, 1, pad)
        got.append(lp)
    for n in range(75, 80):
        lp, cache = decode(model, params, cache, {
            2: (n, 1, toks[n]), 0: (n - 55, 32, other[n - 55])})
        got.append(lp[2:3])
        assert np.abs(lp[0] - want(params, other[:n - 54], [n - 55])[0]
                      ).max() < ROUNDING
    return np.concatenate(got)


@pytest.fixture(scope="module")
def answer():
    """One served prompt for the cases below: (params, tokens, what the
    model gave at every position)."""
    model, params = build()
    toks, other = tokens_of(80, 1), tokens_of(40, 2)
    return params, toks, served(model, params, toks, other)


def test_prefill_in_chunks_then_decode_is_the_reference(answer):
    """Inside the window (positions < 24) and past it; the first chunk
    holds the band's edge inside itself (query 24 no longer sees key 0), the
    second and third hold it inside the cached prefix, the decode steps walk
    from block (n - 24) // 8.  float32 on both sides: what is left between
    the program (paged attention by chunks and decode steps, experts sorted
    and grouped) and the reference (one full forward, every expert on every
    token) is the order of the sums - 4e-6 here."""
    params, toks, got = answer
    ref = want(params, toks, np.arange(80))
    assert np.abs(got - ref).max() < ROUNDING
    assert np.abs(got[:WINDOW] - ref[:WINDOW]).max() < ROUNDING


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_reference_that_got_one_thing_wrong_is_far(answer, control):
    """The reference with every layer full, with plain RoPE in the full
    layers, with the window a block too long: each is 1.0 and more from the
    served model past the window - and, but for the rope, which every
    position feels, equal to it inside the window."""
    params, toks, got = answer
    wrong = want(params, toks, np.arange(80), {**TINY, **CONTROLS[control]})
    assert np.abs(got - wrong)[WINDOW + BS:].max() > 0.5
    if "rope" not in control:
        assert np.abs(got - wrong)[:WINDOW].max() < ROUNDING


def test_a_cache_one_precision_down_is_far(answer):
    """Keys and values rounded to float8 e4m3, as a cache one precision
    below the configuration's would hold them."""
    params, toks, got = answer
    wrong = want(params, toks, np.arange(80), kv_round=KV_ROUND)
    assert np.abs(got - wrong).max() > 0.5


@pytest.mark.parametrize("cut", [8, 24, 40, 56, 72])
def test_a_prompt_split_at_any_block_is_the_unsplit_prompt(cut):
    """75 tokens as [0, cut) and [cut, 75): the band's edge falls in the
    first chunk, on the boundary (cut 24: the second chunk's first query sees
    key 1 and not key 0) or in the second."""
    model, params = build()
    toks, other = tokens_of(80, 3), tokens_of(40, 4)
    pad = max(1 << (75 - cut - 1).bit_length(), 8)
    got = served(model, params, toks, other,
                 chunks=((0, cut, None), (cut, 75, pad)))
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING


def test_the_static_prefix_bucket_moves_nothing_past_what_it_must_cover():
    """A chunk against prefix_blocks 4 (its true prefix) and 8 (the next
    bucket): the XLA form sizes its gather by it, the mask is by position."""
    model, params = build()
    toks = tokens_of(64, 5)
    cache = model.init_kv_cache(NB, BS)
    _, cache = chunk(model, params, cache, toks, 0, 32, 1)
    tight, _ = chunk(model, params, cache, toks, 32, 64, 1, prefix_blocks=4)
    wide, _ = chunk(model, params, cache, toks, 32, 64, 1, prefix_blocks=8)
    assert np.abs(tight - wide).max() < 1e-5
    assert np.abs(tight - want(params, toks, np.arange(32, 64))).max() < ROUNDING


def test_the_ragged_layout_gives_the_same_rows():
    """Two prompts' chunks on one flat token axis (the batched scheduler's
    layout): a chunk deep in a prompt, past the window, beside a fresh one."""
    model, params = build()
    a, b = tokens_of(64, 6), tokens_of(24, 7)
    cache = model.init_kv_cache(NB, BS)
    _, cache = chunk(model, params, cache, a, 0, 40, 1)
    t = 64
    tok = np.zeros((1, t), np.int32)
    pos = np.zeros((1, t), np.int32)
    slots = np.full((1, t), -1, np.int32)
    seq_ids = np.full((1, t), -1, np.int32)
    bt = np.stack([1 + np.arange(WIDTH), 32 + np.arange(WIDTH)]).astype(np.int32)
    for row, (toks, lo, hi, off) in enumerate(((a, 40, 64, 0), (b, 0, 24, 24))):
        at = np.arange(lo, hi)
        tok[0, off:off + hi - lo], pos[0, off:off + hi - lo] = toks[lo:hi], at
        slots[0, off:off + hi - lo] = bt[row, at // BS] * BS + at % BS
        seq_ids[0, off:off + hi - lo] = row
    h, _ = model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray([64, 24], jnp.int32), jnp.asarray(slots), prefix_blocks=8,
        ragged=(jnp.asarray(seq_ids), jnp.asarray([40, 0], jnp.int32),
                jnp.asarray([0, 24], jnp.int32)))
    got = logp(model, params, h[0])
    assert np.abs(got[:24] - want(params, a, np.arange(40, 64))).max() < ROUNDING
    assert np.abs(got[24:48] - want(params, b, np.arange(24))).max() < ROUNDING


def test_the_period_is_one_scan_with_its_layers_unrolled():
    """The lowered decode step of the toy: one loop over the two periods,
    and in its body four attention calls under their kinds' scopes."""
    model, params = build()
    cache = model.init_kv_cache(NB, BS)
    i32 = jnp.int32
    args = (jnp.zeros((4, 1), i32), jnp.zeros((4, 1), i32), cache,
            jnp.zeros((4, WIDTH), i32), jnp.ones((4,), i32),
            jnp.zeros((4, 1), i32))
    jaxpr = jax.make_jaxpr(lambda p, *a: model.forward(p, *a))(params, *args)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2
    text = jax.jit(lambda p, *a: model.forward(p, *a)).lower(
        params, *args).as_text(debug_info=True)
    assert text.count("attn/window") > 0 and text.count("attn/full") > 0


def test_a_stack_of_one_kind_has_no_period_and_no_kind_scope():
    """Mistral's uniform window still goes the way it went: one scan over
    the layers, no ``window`` / ``full`` scope, the one rope."""
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(sliding_window=16)
    model = LlamaModel(cfg)
    assert model.period is None and model.kind_ropes == {}
    assert model.supports_seq_parallel
    params = model.init_params(jax.random.PRNGKey(0))
    cache = model.init_kv_cache(16, 8)
    i32 = jnp.int32
    args = (jnp.zeros((2, 1), i32), jnp.zeros((2, 1), i32), cache,
            jnp.zeros((2, 8), i32), jnp.ones((2,), i32), jnp.zeros((2, 1), i32))
    text = jax.jit(lambda p, *a: model.forward(p, *a)).lower(
        params, *args).as_text(debug_info=True)
    assert "attn/window" not in text and "attn/full" not in text
    jaxpr = jax.make_jaxpr(lambda p, *a: model.forward(p, *a))(params, *args)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == cfg.num_layers
