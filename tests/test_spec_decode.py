"""Prompt-lookup speculative decoding: proposer, greedy-exactness,
rejection-sampled verify under temperature (incl. seeded-stream
identity spec on/off), and acceptance/dispatch-reduction on a
deterministic model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.engine.spec import propose_ngram
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel


# ------------------------------------------------------------- proposer ----
def test_propose_ngram_basic():
    #       0  1  2  3  4  5  6
    toks = [1, 2, 3, 9, 1, 2, 3]
    assert propose_ngram(toks, 3, 2) == [9, 1]
    assert propose_ngram(toks, 3, 5) == [9, 1, 2, 3]
    assert propose_ngram([1, 2, 3, 4], 3, 2) == []          # no recurrence
    # overlapping repeats: an earlier match with a full-k continuation
    # beats the nearest match's truncated tail
    assert propose_ngram([7, 7, 7, 7], 2, 2) == [7, 7]
    assert propose_ngram([7, 7, 7, 7, 7], 2, 2) == [7, 7]
    assert propose_ngram([], 3, 2) == []
    assert propose_ngram([1], 3, 2) == []


def test_propose_ngram_prefers_recent_and_longest():
    # suffix [5,6] occurs twice; the most recent earlier occurrence wins
    toks = [5, 6, 1, 5, 6, 2, 5, 6]
    assert propose_ngram(toks, 2, 1) == [2]
    # longer suffix match preferred over shorter
    toks = [9, 5, 6, 3, 2, 5, 6, 3]  # suffix [5,6,3] matched at idx 1
    assert propose_ngram(toks, 3, 1) == [2]


# ------------------------------------------------- deterministic cycle model
CYCLE = [11, 12, 13, 14]


class CycleModel:
    """Minimal engine-compatible model: argmax at position p is
    CYCLE[p % len(CYCLE)] regardless of input — generation is a known
    repeating stream, so n-gram proposals become perfect after one cycle."""

    def __init__(self, vocab=64, scale=1.0):
        # ``scale`` sharpens the one-hot logits: at scale >= 20 sampling
        # at moderate temperature is effectively deterministic, which the
        # temperature-speculation tests rely on
        self.config = ModelConfig.tiny(vocab_size=vocab)
        self.scale = scale

    def init_params(self):
        return {"zero": jnp.zeros((1,))}

    def init_kv_cache(self, num_blocks, block_size, dtype=None):
        cfg = self.config
        return jnp.zeros(
            (cfg.num_layers, num_blocks, 2, block_size,
             cfg.num_kv_heads * cfg.head_dim), jnp.float32,
        )

    def forward(self, params, tokens, positions, cache, block_tables,
                seq_lens, slot_idx, prefix_blocks=None):
        b, s = tokens.shape
        # encode each token's position into its hidden row
        hidden = jnp.zeros((b, s, self.config.hidden_size), jnp.float32)
        hidden = hidden.at[:, :, 0].set(positions.astype(jnp.float32))
        return hidden, cache

    def compute_logits(self, params, hidden):
        pos = hidden[..., 0].astype(jnp.int32)
        cyc = jnp.asarray(CYCLE, jnp.int32)
        nxt = cyc[(pos + 1) % len(CYCLE)]
        return jax.nn.one_hot(
            nxt, self.config.vocab_size, dtype=jnp.float32
        ) * self.scale


def _run(core, prompt, n, rid="s"):
    outs = []
    core.submit(EngineRequest(
        request_id=rid, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=outs.append,
    ))
    for _ in range(400):
        if not core.step():
            break
    return [t for o in outs for t in o.token_ids]


def _cfg(**kw):
    return EngineConfig(max_batch_size=2, max_model_len=256, block_size=16,
                        num_blocks=40, **kw)


def test_spec_accepts_on_cyclic_model():
    model = CycleModel()
    params = model.init_params()
    # prompt already contains one full cycle so lookup matches immediately
    prompt = [11, 12, 13, 14, 11, 12, 13, 14]
    base = EngineCore(model, params, _cfg(), eos_token_ids=[])
    want = _run(base, prompt, 24, "base")
    spec = EngineCore(model, params, _cfg(spec_tokens=4), eos_token_ids=[])
    got = _run(spec, prompt, 24, "spec")
    assert got == want  # greedy-exact
    assert spec.counts.spec_steps > 0
    assert spec.counts.spec_accepted > 0
    # perfect proposals: ~5 tokens per dispatch vs 1 for the base engine
    assert spec.decode_steps < base.decode_steps / 2
    accept_rate = spec.counts.spec_accepted / max(spec.counts.spec_proposed, 1)
    assert accept_rate > 0.9, (spec.counts.spec_accepted, spec.counts.spec_proposed)


def test_spec_greedy_exact_on_real_model():
    """On a real tiny Llama (arbitrary argmax) speculation may accept
    little, but output must equal plain greedy decoding exactly."""
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # a prompt with internal repetition to give the proposer material
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 9, 10]
    base = EngineCore(model, params, _cfg(), eos_token_ids=[])
    want = _run(base, prompt, 20, "b")
    spec = EngineCore(model, params, _cfg(spec_tokens=3), eos_token_ids=[])
    got = _run(spec, prompt, 20, "s")
    assert got == want
    assert spec.counts.spec_steps > 0  # proposals were attempted


def test_spec_defers_to_sampler_features():
    """A request using a feature the verify pass can't thread (penalties,
    logprobs, grammar) disables the speculative path for that dispatch —
    the plain decode step runs instead.  (Plain temperature no longer defers:
    the verify pass samples.)"""
    model = CycleModel()
    params = model.init_params()
    core = EngineCore(model, params, _cfg(spec_tokens=4), eos_token_ids=[])
    outs = []
    core.submit(EngineRequest(
        request_id="t", prompt=[11, 12, 13, 14, 11, 12, 13, 14],
        sampling=SamplingOptions(temperature=1.0, frequency_penalty=0.5),
        stops=StopConditions(max_tokens=8, ignore_eos=True),
        emit=outs.append,
    ))
    for _ in range(100):
        if not core.step():
            break
    assert sum(len(o.token_ids) for o in outs) == 8
    assert core.counts.spec_steps == 0


def test_spec_accepts_under_temperature():
    """Sampled verify: with sharp logits, temperature sampling is
    effectively deterministic, so proposals accept and the stream is the
    cycle — speculation must engage (it used to require greedy)."""
    model = CycleModel(scale=25.0)
    params = model.init_params()
    core = EngineCore(model, params, _cfg(spec_tokens=4), eos_token_ids=[])
    outs = []
    core.submit(EngineRequest(
        request_id="t", prompt=[11, 12, 13, 14, 11, 12, 13, 14],
        sampling=SamplingOptions(temperature=0.7),
        stops=StopConditions(max_tokens=16, ignore_eos=True),
        emit=outs.append,
    ))
    for _ in range(200):
        if not core.step():
            break
    got = [t for o in outs for t in o.token_ids]
    assert len(got) == 16
    # positions 8.. continue the cycle deterministically at scale 25
    assert got == [CYCLE[(8 + j) % 4] for j in range(16)]
    assert core.counts.spec_steps > 0
    assert core.counts.spec_accepted > 0


@pytest.mark.parametrize("scale", [1.0, 25.0])
def test_spec_seeded_stream_identical(scale):
    """A seeded request's stream is BIT-IDENTICAL with speculation on or
    off, at any temperature: seeded noise is a pure function of (seed,
    position, token id), and the verify pass reuses it per position.
    scale=1.0 makes sampling near-uniform (proposals mostly rejected);
    scale=25 makes it near-deterministic (mostly accepted) — equality
    must hold in both regimes."""
    def run(spec_tokens, rid):
        model = CycleModel(scale=scale)
        core = EngineCore(
            model, model.init_params(),
            _cfg(spec_tokens=spec_tokens), eos_token_ids=[],
        )
        outs = []
        core.submit(EngineRequest(
            request_id=rid, prompt=[11, 12, 13, 14, 11, 12, 13, 14],
            sampling=SamplingOptions(temperature=0.9, seed=1234),
            stops=StopConditions(max_tokens=24, ignore_eos=True),
            emit=outs.append,
        ))
        for _ in range(400):
            if not core.step():
                break
        return [t for o in outs for t in o.token_ids], core

    base, _ = run(0, "off")
    spec, core = run(4, "on")
    assert len(base) == 24
    assert spec == base
    assert core.counts.spec_steps > 0


def test_spec_respects_block_limits():
    """Proposals are clamped to the sequence's block space; running out
    finishes at LENGTH exactly like the plain decode step."""
    model = CycleModel()
    params = model.init_params()
    core = EngineCore(
        model, params,
        EngineConfig(max_batch_size=1, max_model_len=48, block_size=16,
                     num_blocks=3, spec_tokens=4),
        eos_token_ids=[],
    )
    outs = []
    core.submit(EngineRequest(
        request_id="lim", prompt=[11, 12, 13, 14] * 3,
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=100, ignore_eos=True),
        emit=outs.append,
    ))
    for _ in range(200):
        if not core.step():
            break
    assert outs[-1].finish_reason is not None
    total = 12 + sum(len(o.token_ids) for o in outs)
    assert total <= 48


@pytest.mark.parametrize("marked_rows", [1, 3])
def test_spec_serves_rows_that_propose_nothing(monkeypatch, marked_rows):
    """Whatever share of the rows proposes, the verify dispatch engages
    and a row without a proposal takes its one token from it: every row
    ends with the tokens it asked for.  (Proposals are stubbed: only
    prompts starting with the marker token propose.)"""
    import dynamo_tpu.engine.spec as spec_mod

    MARK = 11

    def stub(tokens, ngram, k, min_ngram=1):
        return [12, 13] if tokens and tokens[0] == MARK else []

    monkeypatch.setattr(spec_mod, "propose_ngram", stub)
    model = CycleModel()
    core = EngineCore(
        model, model.init_params(),
        EngineConfig(max_batch_size=4, max_model_len=256, block_size=16,
                     num_blocks=64, spec_tokens=4),
        eos_token_ids=[],
    )
    outs = {}
    for j in range(4):
        rid = f"r{j}"
        outs[rid] = []
        first = MARK if j < marked_rows else 40 + 5 * j
        core.submit(EngineRequest(
            request_id=rid, prompt=[first, 31 + j, 32 + j],
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=12, ignore_eos=True),
            emit=outs[rid].append,
        ))
    for _ in range(300):
        if not core.step():
            break
    for rid, lst in outs.items():
        assert sum(len(o.token_ids) for o in lst) == 12, rid
    assert core.counts.spec_steps > 0


# ------------------------------------------------------ draft-model spec ----
def _drain_engine(core, prompt, n, rid="d", **samp):
    outs = []
    core.submit(EngineRequest(
        request_id=rid, prompt=list(prompt),
        sampling=SamplingOptions(**samp),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=outs.append,
    ))
    for _ in range(600):
        if not core.step():
            break
    return [t for o in outs for t in o.token_ids]


def test_draft_model_identical_to_target_accepts_everything():
    """Draft == target: every greedy proposal verifies, so the stream is
    plain greedy decoding at ~1/(k+1) the target dispatches."""
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [5, 6, 7, 8, 9]

    base = EngineCore(model, params, _cfg(), eos_token_ids=[])
    want = _drain_engine(base, prompt, 24, "b", temperature=0.0)

    spec = EngineCore(model, params, _cfg(spec_tokens=4), eos_token_ids=[],
                      draft=(model, params))
    got = _drain_engine(spec, prompt, 24, "s", temperature=0.0)
    assert got == want
    assert spec.draft is not None and spec.draft.dispatches > 0
    assert spec.counts.spec_steps > 0
    accept = spec.counts.spec_accepted / max(spec.counts.spec_proposed, 1)
    assert accept > 0.9, (spec.counts.spec_accepted, spec.counts.spec_proposed)
    # dispatch win: ~24/(k+1) verify steps instead of 24 decode steps
    assert spec.decode_steps < base.decode_steps / 2


def test_draft_model_different_weights_still_exact():
    """A DIFFERENT draft (other random weights) proposes mostly-wrong
    tokens; acceptance is low but the emitted stream must still equal
    plain decoding exactly — greedy and seeded."""
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    draft_params = model.init_params(jax.random.PRNGKey(99))
    prompt = [3, 1, 4, 1, 5]

    for samp in ({"temperature": 0.0}, {"temperature": 0.8, "seed": 42}):
        base = EngineCore(model, params, _cfg(), eos_token_ids=[])
        want = _drain_engine(base, prompt, 16, "b", **samp)
        spec = EngineCore(model, params, _cfg(spec_tokens=3),
                          eos_token_ids=[], draft=(model, draft_params))
        got = _drain_engine(spec, prompt, 16, "s", **samp)
        assert got == want, samp
        assert spec.counts.spec_steps > 0


def test_draft_blocks_released_on_finish():
    """Draft blocks recycle across requests — a long sequence of short
    requests must not exhaust the draft pool."""
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    core = EngineCore(model, params, _cfg(spec_tokens=2), eos_token_ids=[],
                      draft=(model, params))
    free0 = len(core.draft._free)
    for j in range(6):
        out = _drain_engine(core, [7 + j, 8, 9], 4, f"r{j}",
                            temperature=0.0)
        assert len(out) == 4
    assert len(core.draft._free) == free0
    assert core.draft._blocks == {}


def test_draft_vocab_mismatch_rejected():
    model = LlamaModel(ModelConfig.tiny())
    other = LlamaModel(ModelConfig.tiny(vocab_size=128))
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        EngineCore(model, params, _cfg(spec_tokens=2), eos_token_ids=[],
                   draft=(other, other.init_params(jax.random.PRNGKey(1))))


def test_draft_grow_all_or_nothing():
    """A row that cannot FULLY grow takes nothing — partial grabs would
    strand pool blocks on rows that can never draft."""
    from dynamo_tpu.engine.draft import DraftProposer

    model = CycleModel()
    cfg = EngineConfig(max_batch_size=2, max_model_len=256, block_size=16,
                       num_blocks=4)
    d = DraftProposer(model, model.init_params(), cfg)
    assert d._grow(0, 16 * 3)        # 3 of 4 blocks
    assert not d._grow(1, 16 * 2)    # needs 2, only 1 free
    assert len(d._free) == 1         # nothing stranded
    assert d._blocks.get(1, []) == []


def test_draft_long_prompt_catches_up_across_steps():
    """A prompt longer than the ingest bucket catches up via batched
    chunked dispatches (at most one per propose call) and then drafts —
    output still equals plain greedy decoding."""
    cfg = ModelConfig.tiny(max_position_embeddings=2048)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [(i * 17) % 200 + 1 for i in range(1100)]  # > 2 chunks

    def ecfg(**kw):
        return EngineConfig(max_batch_size=2, max_model_len=1536,
                            block_size=16, num_blocks=128, **kw)

    base = EngineCore(model, params, ecfg(), eos_token_ids=[])
    want = _drain_engine(base, prompt, 10, "b", temperature=0.0)
    spec = EngineCore(model, params, ecfg(spec_tokens=3), eos_token_ids=[],
                      draft=(model, params))
    got = _drain_engine(spec, prompt, 10, "s", temperature=0.0)
    assert got == want
    assert spec.counts.spec_steps > 0


def test_draft_model_with_int8_caches_still_exact():
    """Draft speculation with int8 TARGET and DRAFT caches (the
    HBM-tight 8B-on-one-chip shape, engine/draft.py): the quantized
    draft cache only shifts PROPOSALS; the stream must equal the plain
    int8-cache engine exactly — greedy and seeded."""
    from dynamo_tpu.ops.kv_quant import is_quant

    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [3, 1, 4, 1, 5]

    for samp in ({"temperature": 0.0}, {"temperature": 0.8, "seed": 7}):
        base = EngineCore(model, params, _cfg(cache_dtype="int8"),
                          eos_token_ids=[])
        want = _drain_engine(base, prompt, 16, "b", **samp)
        spec = EngineCore(model, params,
                          _cfg(spec_tokens=3, cache_dtype="int8"),
                          eos_token_ids=[], draft=(model, params))
        assert is_quant(spec.cache) and is_quant(spec.draft.cache)
        got = _drain_engine(spec, prompt, 16, "s", **samp)
        assert got == want, samp
        assert spec.counts.spec_steps > 0
