"""JSON mode through the real engine: grammar-masked sampling inside the
decode step and the prefill first-token path, with the host advancing
request state between dispatches."""

import json

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.grammar import JsonGrammar
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import FinishReason, SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

EOS = 2


@pytest.fixture(scope="module")
def setup():
    import jax

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2,
        max_position_embeddings=256, rope_theta=10000.0, dtype="float32",
    )
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # vocab: ids 3..258 = single bytes 0..255; a few multibyte; rest None
    toks: list = [None] * 512
    for b in range(256):
        toks[3 + b] = bytes([b])
    toks[300] = b'{"'
    toks[301] = b'":'
    toks[302] = b'"}'
    toks[303] = b'true'
    toks[304] = b'[1,'
    toks[305] = b'23'
    grammar = JsonGrammar.from_token_bytes(toks, eos_ids=[EOS])
    return model, params, grammar, toks


def run_one(core, toks, *, temperature, max_tokens=48, rid="j1", prompt=None):
    outs = []
    req = EngineRequest(
        request_id=rid,
        prompt=prompt or [5, 6, 7, 8],
        sampling=SamplingOptions(temperature=temperature, json_mode=True),
        stops=StopConditions(max_tokens=max_tokens),
        emit=outs.append,
    )
    core.submit(req)
    for _ in range(600):
        if not core.step():
            break
    assert outs and outs[-1].finish_reason is not None
    ids = [t for o in outs for t in o.token_ids]
    return ids, outs[-1].finish_reason


def decode(toks, ids):
    return b"".join(toks[i] for i in ids if i != EOS and toks[i])


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_json_mode_emits_valid_json(setup, temperature):
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS],
                      grammar=grammar)
    for trial in range(3):
        ids, reason = run_one(core, toks, temperature=temperature,
                              rid=f"j{temperature}-{trial}",
                              prompt=[5 + trial, 6, 7, 8])
        text = decode(toks, ids).decode("utf-8", errors="replace")
        if reason is FinishReason.EOS:
            json.loads(text)  # complete -> must parse
        else:  # LENGTH: still a valid JSON *prefix* — never malformed
            assert reason is FinishReason.LENGTH
            # replay through the automaton: every step must be maskable
            tb = grammar.tables
            s, d, st = 1, 0, 0
            from dynamo_tpu.engine.grammar import INIT_STATE

            s = INIT_STATE
            for t in ids:
                if t == EOS:
                    break
                assert tb.valid_mask(s, d, st)[t], f"token {t} out of grammar"
                s, d, st = tb.advance(s, d, st, t)


def test_json_mode_with_penalties_and_topk(setup):
    """Grammar + penalties + top-k in the same decode step."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    outs = []
    req = EngineRequest(
        request_id="jp",
        prompt=[9, 10, 11],
        sampling=SamplingOptions(temperature=0.8, top_k=40,
                                 frequency_penalty=0.4, presence_penalty=0.2,
                                 json_mode=True),
        stops=StopConditions(max_tokens=40),
        emit=outs.append,
    )
    core.submit(req)
    for _ in range(400):
        if not core.step():
            break
    assert outs and outs[-1].finish_reason is not None
    ids = [t for o in outs for t in o.token_ids]
    text = decode(toks, ids).decode("utf-8", errors="replace")
    if outs[-1].finish_reason is FinishReason.EOS:
        json.loads(text)


def test_json_mode_mixed_batch(setup):
    """A json_mode request and a free-running request decode in the same
    dispatch; only the constrained row is masked."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    outs_j, outs_f = [], []
    core.submit(EngineRequest(
        request_id="json", prompt=[5, 6, 7],
        sampling=SamplingOptions(temperature=1.0, json_mode=True),
        stops=StopConditions(max_tokens=32), emit=outs_j.append,
    ))
    core.submit(EngineRequest(
        request_id="free", prompt=[8, 9, 10],
        sampling=SamplingOptions(temperature=1.0),
        stops=StopConditions(max_tokens=32, ignore_eos=True),
        emit=outs_f.append,
    ))
    for _ in range(600):
        if not core.step():
            break
    assert outs_j[-1].finish_reason is not None
    assert outs_f[-1].finish_reason is not None
    ids_j = [t for o in outs_j for t in o.token_ids]
    text = decode(toks, ids_j).decode("utf-8", errors="replace")
    if outs_j[-1].finish_reason is FinishReason.EOS:
        json.loads(text)
    # the free request generated the full 32 tokens unconstrained
    assert sum(len(o.token_ids) for o in outs_f) == 32


def test_json_mode_rejected_without_grammar(setup):
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS])  # no grammar
    outs = []
    core.submit(EngineRequest(
        request_id="nog", prompt=[5, 6],
        sampling=SamplingOptions(json_mode=True),
        stops=StopConditions(max_tokens=8), emit=outs.append,
    ))
    for _ in range(20):
        if not core.step():
            break
    assert outs and outs[-1].finish_reason is FinishReason.ERROR


def test_json_mode_rejected_without_usable_eos(setup):
    """Grammar compiled with no EOS id (or one outside the model vocab)
    cannot terminate JSON mode — requests are rejected, not garbled."""
    model, params, _, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    no_eos = JsonGrammar.from_token_bytes(toks, eos_ids=[])
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=no_eos)
    outs = []
    core.submit(EngineRequest(
        request_id="noeos", prompt=[5, 6],
        sampling=SamplingOptions(json_mode=True),
        stops=StopConditions(max_tokens=8), emit=outs.append,
    ))
    for _ in range(20):
        if not core.step():
            break
    assert outs and outs[-1].finish_reason is FinishReason.ERROR


def test_guided_choice_emits_a_choice(setup):
    """guided_choice through the real engine: output is exactly one of
    the candidate strings, at any temperature."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    choices = ["alpha", "beta", "true"]
    for trial, temp in enumerate([0.0, 1.0, 1.0]):
        outs = []
        core.submit(EngineRequest(
            request_id=f"gc{trial}", prompt=[5 + trial, 6, 7],
            sampling=SamplingOptions(temperature=temp,
                                     guided_choice=list(choices)),
            stops=StopConditions(max_tokens=16),
            emit=outs.append,
        ))
        for _ in range(200):
            if not core.step():
                break
        assert outs[-1].finish_reason is FinishReason.EOS
        ids = [t for o in outs for t in o.token_ids]
        text = decode(toks, ids).decode()
        assert text in choices, text


def test_mixed_grammar_batch_json_and_choices(setup):
    """One dispatch with a JSON row, two different choice rows, and a free
    row: each obeys its own grammar (composite tables, offset-mapped)."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=8, num_blocks=96,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    outs = {r: [] for r in ("json", "c1", "c2", "free")}
    core.submit(EngineRequest(
        request_id="json", prompt=[5, 6, 7],
        sampling=SamplingOptions(temperature=1.0, json_mode=True),
        stops=StopConditions(max_tokens=24), emit=outs["json"].append,
    ))
    core.submit(EngineRequest(
        request_id="c1", prompt=[8, 9],
        sampling=SamplingOptions(temperature=1.0,
                                 guided_choice=["yes", "no"]),
        stops=StopConditions(max_tokens=12), emit=outs["c1"].append,
    ))
    core.submit(EngineRequest(
        request_id="c2", prompt=[10, 11],
        sampling=SamplingOptions(temperature=1.0,
                                 guided_choice=["left", "right", "up"]),
        stops=StopConditions(max_tokens=12), emit=outs["c2"].append,
    ))
    core.submit(EngineRequest(
        request_id="free", prompt=[12, 13],
        sampling=SamplingOptions(temperature=1.0),
        stops=StopConditions(max_tokens=12, ignore_eos=True),
        emit=outs["free"].append,
    ))
    for _ in range(600):
        if not core.step():
            break
    for rid, lst in outs.items():
        assert lst and lst[-1].finish_reason is not None, rid
    ids = lambda r: [t for o in outs[r] for t in o.token_ids]
    assert decode(toks, ids("c1")).decode() in ("yes", "no")
    assert decode(toks, ids("c2")).decode() in ("left", "right", "up")
    if outs["json"][-1].finish_reason is FinishReason.EOS:
        json.loads(decode(toks, ids("json")).decode("utf-8", errors="replace")
                   if isinstance(decode(toks, ids("json")), bytes)
                   else decode(toks, ids("json")))
    assert sum(len(o.token_ids) for o in outs["free"]) == 12


def test_grammar_budget_backpressure(setup):
    """Requests whose combined grammar states would overflow the composite
    budget WAIT for slots instead of crashing the engine step."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=8, num_blocks=96,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    core.GRAMMAR_STATE_BUDGET = 300  # tiny budget for the test
    big = ["x" * 120, "y" * 120]     # bound ~242 states each set
    outs = {r: [] for r in ("a", "b")}
    for rid in ("a", "b"):
        core.submit(EngineRequest(
            request_id=rid, prompt=[5, 6],
            sampling=SamplingOptions(
                temperature=0.0,
                guided_choice=[c + rid for c in big],  # distinct sets
            ),
            stops=StopConditions(max_tokens=200),
            emit=outs[rid].append,
        ))
    for _ in range(1500):
        if not core.step():
            break
    # both finish (serialized through the budget), neither errors
    for rid in ("a", "b"):
        assert outs[rid] and outs[rid][-1].finish_reason is FinishReason.EOS
        text = decode(toks, [t for o in outs[rid] for t in o.token_ids]).decode()
        assert text in [c + rid for c in big]


def test_guided_regex_through_engine(setup):
    """guided_regex end to end: output fullmatches the pattern at any
    temperature, terminating at EOS."""
    import re

    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    pattern = r"(up|down) [0-9][0-9]?%"
    for trial in range(3):
        outs = []
        core.submit(EngineRequest(
            request_id=f"rx{trial}", prompt=[5 + trial, 6],
            sampling=SamplingOptions(temperature=1.0, guided_regex=pattern),
            stops=StopConditions(max_tokens=24),
            emit=outs.append,
        ))
        for _ in range(300):
            if not core.step():
                break
        assert outs[-1].finish_reason is FinishReason.EOS
        text = decode(toks, [t for o in outs for t in o.token_ids]).decode()
        assert re.fullmatch(pattern, text), text


def test_guided_regex_bad_pattern_errors_request_not_engine(setup):
    """A pattern that blows the DFA cap ERROR-finishes that request; the
    engine keeps serving others."""
    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    import dynamo_tpu.engine.grammar as gmod

    # force a tiny DFA cap so an ordinary pattern trips it
    old = gmod.MAX_REGEX_STATES
    gmod.MAX_REGEX_STATES = 3
    try:
        outs_bad, outs_ok = [], []
        core.submit(EngineRequest(
            request_id="bad", prompt=[5, 6],
            sampling=SamplingOptions(guided_regex="abcdefgh"),
            stops=StopConditions(max_tokens=8), emit=outs_bad.append,
        ))
        core.submit(EngineRequest(
            request_id="ok", prompt=[7, 8],
            sampling=SamplingOptions(temperature=0.0),
            stops=StopConditions(max_tokens=4, ignore_eos=True),
            emit=outs_ok.append,
        ))
        for _ in range(100):
            if not core.step():
                break
        assert outs_bad[-1].finish_reason is FinishReason.ERROR
        assert sum(len(o.token_ids) for o in outs_ok) == 4
    finally:
        gmod.MAX_REGEX_STATES = old


def test_schema_regex_falls_back_to_json_mode(setup):
    """A schema-derived regex whose DFA exceeds the cap degrades to the
    generic JSON grammar instead of failing the request."""
    import dynamo_tpu.engine.grammar as gmod

    model, params, grammar, toks = setup
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, eos_token_ids=[EOS], grammar=grammar)
    old = gmod.MAX_REGEX_STATES
    gmod.MAX_REGEX_STATES = 3  # force the overflow
    try:
        outs = []
        core.submit(EngineRequest(
            request_id="sf", prompt=[5, 6, 7],
            sampling=SamplingOptions(temperature=1.0, json_mode=True,
                                     guided_regex="abcdefgh"),
            stops=StopConditions(max_tokens=24), emit=outs.append,
        ))
        for _ in range(300):
            if not core.step():
                break
        assert outs[-1].finish_reason in (FinishReason.EOS,
                                          FinishReason.LENGTH)
        ids = [t for o in outs for t in o.token_ids]
        # output obeys the JSON grammar (fallback), replayed host-side
        from dynamo_tpu.engine.grammar import INIT_STATE

        tb = grammar.tables
        s, d, st = INIT_STATE, 0, 0
        for t in ids:
            if t == EOS:
                break
            assert tb.valid_mask(s, d, st)[t]
            s, d, st = tb.advance(s, d, st, t)
    finally:
        gmod.MAX_REGEX_STATES = old


def test_json_mode_under_tp_mesh(setup):
    """Grammar masking composes with tensor parallelism: sharded logits,
    replicated tables, one valid JSON out."""
    import jax
    import numpy as np_
    from dynamo_tpu.utils.mesh import MESH_AXES, build_mesh

    model, params, grammar, toks = setup
    mesh = build_mesh((1, 2), MESH_AXES)
    cfg = EngineConfig(
        max_batch_size=2, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128],
    )
    core = EngineCore(model, params, cfg, mesh=mesh, eos_token_ids=[EOS],
                      grammar=grammar)
    ids, reason = run_one(core, toks, temperature=1.0, rid="mesh")
    text = decode(toks, ids).decode("utf-8", errors="replace")
    if reason is FinishReason.EOS:
        json.loads(text)
    else:
        from dynamo_tpu.engine.grammar import INIT_STATE

        tb = grammar.tables
        s, d, st = INIT_STATE, 0, 0
        for t in ids:
            if t == EOS:
                break
            assert tb.valid_mask(s, d, st)[t]
            s, d, st = tb.advance(s, d, st, t)
