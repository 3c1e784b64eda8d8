"""Double-buffered (lookahead) dispatch: fused multi-turn bursts with
on-device stop/append folding plus speculative next-turn host prebuild
must be invisible to callers — seeded-stream parity against the unified
single-turn scheduler (tokens, logprobs, cached_tokens, grammar,
penalties, seeds, int8 cache), the ONE-device_get-per-burst win, the
mispredict patch-and-discard path, the host-gap drop with overlap
attribution, the /metrics counters, and the compile-once census."""

import json

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.grammar import JsonGrammar
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs.timeline import step_timeline

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
    """Same deterministic-stream mix as the unified-dispatch gate: a
    long prompt that stays mid-chunk across turns, grammar-constrained
    decoding, seeded sampling with top_logprobs, penalties, and a plain
    greedy request — every stream must be token-identical whether a
    mixed turn dispatches one device step or a fused burst."""
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


def test_mixed_workload_parity_lookahead(setup):
    """The tentpole gate: mixed turns folded into k-step bursts with a
    single trailing device_get produce token-identical output streams vs
    the single-turn unified scheduler — incl. grammar-constrained,
    seeded, penalised and top_logprobs requests (on-device grammar
    advance + penalty append must mirror the host replay exactly)."""
    model, params, grammar = setup
    specs = mixed_specs()
    ref_core = make_core(model, params, grammar, prefill_chunk_tokens=16,
                         prefill_token_budget=64,
                         unified_token_dispatch=True)
    ref = run_staggered(ref_core, specs)
    assert ref_core.lookahead_bursts == 0

    la_core = make_core(model, params, grammar, prefill_chunk_tokens=16,
                        prefill_token_budget=64,
                        lookahead_dispatch=True, decode_steps=8)
    got = run_staggered(la_core, specs)
    # the burst path actually engaged, folding >1 device turn per get
    assert la_core.lookahead_bursts > 0
    assert la_core.lookahead_hits + la_core.lookahead_mispredicts > 0

    assert_stream_parity(specs, ref, got)
    # logprob parity on the top_logprobs request (ids exact, values tight)
    lp_g, lp_r = flat(got["lp"], "logprobs"), flat(ref["lp"], "logprobs")
    np.testing.assert_allclose(lp_g, lp_r, rtol=2e-5, atol=2e-6)
    tg = [t for o in got["lp"] for t in (o.top_logprobs or [])]
    tr = [t for o in ref["lp"] for t in (o.top_logprobs or [])]
    assert [[i for i, _ in step] for step in tg] == \
           [[i for i, _ in step] for step in tr]
    np.testing.assert_allclose(
        [v for step in tg for _, v in step],
        [v for step in tr for _, v in step], rtol=2e-5, atol=2e-6)


def test_pure_workloads_parity_and_no_burst(setup):
    """Pure prefill and pure decode workloads never hit the burst
    entrypoint under the flag (no mixed turns exist) and stay
    token-identical with it on."""
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
        ref_core = make_core(model, params, prefill_token_budget=64,
                             unified_token_dispatch=True)
        ref = run_staggered(ref_core, specs, head=len(specs), stagger=0)
        la_core = make_core(model, params, prefill_token_budget=64,
                            lookahead_dispatch=True, decode_steps=8)
        got = run_staggered(la_core, specs, head=len(specs), stagger=0)
        assert_stream_parity(specs, ref, got)
        assert la_core.lookahead_bursts == 0
        assert la_core._burst_fn._cache_size() == 0


def test_mispredict_mid_burst_patch_and_discard(setup):
    """A stop firing mid-burst (max_tokens lands inside the fused scan)
    must discard the over-generated device samples AND the speculative
    next-turn prebuild: streams stay identical to the single-turn
    scheduler and the mispredict is counted."""
    model, params, _ = setup
    rng = np.random.RandomState(9)
    specs = [
        # 1 token after its prefill turn, then +8 per mixed burst: the
        # 12-token cap lands 3 samples into the second fused scan
        ("deco", [int(x) for x in rng.randint(3, 259, size=8)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=12)),
        ("pref", [int(x) for x in rng.randint(3, 259, size=48)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=1)),
    ]
    ref_core = make_core(model, params, prefill_chunk_tokens=16,
                         prefill_token_budget=64,
                         unified_token_dispatch=True)
    ref = run_staggered(ref_core, specs, head=1, stagger=1)
    la_core = make_core(model, params, prefill_chunk_tokens=16,
                        prefill_token_budget=64,
                        lookahead_dispatch=True, decode_steps=8)
    got = run_staggered(la_core, specs, head=1, stagger=1)
    assert la_core.lookahead_bursts > 0
    assert la_core.lookahead_mispredicts > 0, "stop never fired mid-burst"
    assert_stream_parity(specs, ref, got)


def test_burst_turn_is_one_device_get(setup):
    """THE readback-count win, turn by turn: with one request decoding
    and one mid-prefill, a lookahead step() folds ``decode_steps``
    device turns behind exactly ONE device_get — where the single-turn
    scheduler pays one readback per generated token."""
    model, params, _ = setup
    rng = np.random.RandomState(2)
    k = 4
    deco = EngineRequest(
        "deco", [int(x) for x in rng.randint(3, 259, size=8)],
        SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=40, ignore_eos=True), emit=lambda o: None)
    long_prompt = [int(x) for x in rng.randint(3, 259, size=48)]

    core = make_core(model, params, prefill_chunk_tokens=16,
                     prefill_token_budget=64,
                     lookahead_dispatch=True, decode_steps=k)
    core.submit(deco)
    for _ in range(3):
        core.step()  # deco is now decoding
    pref = EngineRequest("pref", long_prompt, SamplingOptions(temperature=0.0),
                         StopConditions(max_tokens=1), emit=lambda o: None)
    core.submit(pref)
    core.step()  # admission + first mixed burst
    while pref.computed_tokens < pref.prompt_len:
        gen_before = deco.generated
        computed_before = pref.computed_tokens
        gets_before = core.device_gets
        dsteps_before = core.decode_steps
        core.step()
        assert core.device_gets == gets_before + 1     # ONE readback
        assert core.decode_steps == dsteps_before + k  # k device turns
        assert deco.generated == gen_before + k        # k tokens landed
        assert pref.computed_tokens > computed_before  # prefill advanced
    assert core.lookahead_bursts >= 3  # 48 tokens / 16-token chunks


def test_lookahead_int8_cache_parity(setup):
    """The fused burst writes the QuantKvCache (data AND scale pools)
    through the same split row-scatter path per scan step: greedy
    streams match the single-turn unified int8 scheduler token for
    token."""
    model, params, _ = setup
    rng = np.random.RandomState(5)
    specs = [
        ("deco", [int(x) for x in rng.randint(3, 259, size=9)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=6)),
        ("p1", [int(x) for x in rng.randint(3, 259, size=20)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=3)),
    ]
    ref_core = make_core(model, params, prefill_chunk_tokens=16,
                         prefill_token_budget=64, cache_dtype="int8",
                         unified_token_dispatch=True)
    ref = run_staggered(ref_core, specs, head=1, stagger=1)
    la_core = make_core(model, params, prefill_chunk_tokens=16,
                        prefill_token_budget=64, cache_dtype="int8",
                        lookahead_dispatch=True, decode_steps=4)
    got = run_staggered(la_core, specs, head=1, stagger=1)
    assert la_core.lookahead_bursts > 0
    assert_stream_parity(specs, ref, got)


def test_host_gap_drops_and_overlap_attributed(setup):
    """The perf claim behind the feature: for the SAME seeded workload,
    total host-gap seconds (wall outside dispatch+overlap+readback,
    summed over busy steps) drop under lookahead — fewer turn
    boundaries pay admission/build, and the next-turn prebuild runs in
    the overlap window, which must show up as a nonzero ``overlap``
    phase while the phase-sum==wall invariant keeps holding."""
    model, params, _ = setup
    rng = np.random.RandomState(8)
    deco_prompt = [int(x) for x in rng.randint(3, 259, size=8)]
    long_prompt = [int(x) for x in rng.randint(3, 259, size=96)]

    def run(lookahead):
        core = make_core(model, params, prefill_chunk_tokens=16,
                         prefill_token_budget=64, decode_steps=4,
                         unified_token_dispatch=True,
                         lookahead_dispatch=lookahead)
        core.submit(EngineRequest(
            "deco", list(deco_prompt), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=40, ignore_eos=True),
            emit=lambda o: None))
        for _ in range(3):
            core.step()
        core.submit(EngineRequest(
            "pref", list(long_prompt), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=1), emit=lambda o: None))
        # warm every executable OUTSIDE the measured window: compiles
        # inside dispatch would swamp the host-gap comparison
        core.step()
        step_timeline.reset()
        drain(core)
        snap = step_timeline.snapshot()
        return core, step_timeline.host_gap_s_total, snap

    core_off, gap_off, snap_off = run(lookahead=False)
    core_on, gap_on, snap_on = run(lookahead=True)
    assert core_off.lookahead_bursts == 0
    assert core_on.lookahead_bursts > 0
    # prebuild work is attributed to the overlap window, and only there
    assert snap_off["phases"]["overlap"] == 0.0
    assert snap_on["phases"]["overlap"] > 0.0
    # same tokens, fewer turn boundaries, overlapped builds: the total
    # host bubble shrinks (per-turn means are not comparable — lookahead
    # turns carry k tokens of host_post each)
    assert gap_on < gap_off
    # phase attribution stays exhaustive under the new overlap mark
    phase_sum = sum(snap_on["phases"].values())
    assert phase_sum >= 0.95 * snap_on["wall_seconds_total"]


def test_lookahead_gauges_on_http_metrics(setup):
    """The lookahead counters ride /metrics next to the unified gauges."""
    from dynamo_tpu.engine.counters import lookahead_counters
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.obs.metric_names import EngineMetric as EM

    model, params, _ = setup
    lookahead_counters.reset()
    rng = np.random.RandomState(6)
    specs = [
        ("deco", [int(x) for x in rng.randint(3, 259, size=8)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=10)),
        ("p1", [int(x) for x in rng.randint(3, 259, size=16)],
         SamplingOptions(temperature=0.0), StopConditions(max_tokens=2)),
    ]
    core = make_core(model, params, prefill_token_budget=32,
                     lookahead_dispatch=True, decode_steps=4)
    run_staggered(core, specs, head=1, stagger=3)
    assert core.lookahead_bursts > 0
    text = Metrics().render()
    assert (f"{EM.LOOKAHEAD_BURSTS_TOTAL} "
            f"{core.lookahead_bursts}") in text
    assert (f"{EM.LOOKAHEAD_HITS_TOTAL} "
            f"{core.lookahead_hits}") in text
    assert (f"{EM.LOOKAHEAD_MISPREDICTS_TOTAL} "
            f"{core.lookahead_mispredicts}") in text
    assert (f"{EM.LOOKAHEAD_COMMITS_TOTAL} "
            f"{core.lookahead_commits}") in text
    assert (f"{EM.LOOKAHEAD_FLUSHES_TOTAL} "
            f"{core.lookahead_flushes}") in text
    assert f"{EM.LOOKAHEAD_DISPATCH_DEPTH} " in text
    assert f"{EM.HOST_GAP_MS_PER_TURN} " in text


# --------------------------------------------------------------- census


def _runtime_model():
    cfg = ModelConfig(
        vocab_size=16, hidden_size=16, intermediate_size=32, num_layers=1,
        num_heads=2, num_kv_heads=1, head_dim=8,
        max_position_embeddings=128, dtype="float32",
    )
    model = LlamaModel(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_seeded_burst_compiles_once():
    """Census proof for the sixth donated impl: a seeded mixed workload
    compiles the fused burst exactly once for its single touched
    (t, r, pb, num_steps) bucket, and an identical second run triggers
    ZERO further compile events — the speculative prebuild path must not
    smuggle in a retrace."""

    model, params = _runtime_model()

    def drive(core):
        outs = []
        # A reaches decode after one step (1 token so far — the fused
        # decode-only burst hasn't run yet); B arrives while A decodes,
        # so the turn that prefills B is a mixed one — the fused burst
        core.submit(EngineRequest(
            "a", list(range(1, 9)), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=16, ignore_eos=True), outs.append))
        core.step()
        core.submit(EngineRequest(
            "b", list(range(2, 14)), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=4), outs.append))
        for _ in range(64):
            if not core.step():
                break
        return outs

    core = EngineCore(model, params, EngineConfig(
        max_batch_size=2, max_model_len=64, block_size=8, num_blocks=32,
        prefill_buckets=[16, 32, 64], prefill_token_budget=32,
        lookahead_dispatch=True, decode_steps=8, seed=0,
        # prefix reuse off: the rerun must replay a bit-identical
        # dispatch stream (cached prefixes would change the pb buckets)
        enable_prefix_reuse=False,
    ), eos_token_ids=[])
    drive(core)
    assert core.lookahead_bursts >= 1
    assert core._burst_fn._cache_size() == 1

    compile_events = []

    def listener(name, **kw):
        if "compile" in name:
            compile_events.append(name)

    jax.monitoring.register_event_listener(listener)
    try:
        drive(core)  # identical seeded workload, fresh requests
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert compile_events == [], (
        f"second identical run recompiled: {compile_events}"
    )
    assert core._burst_fn._cache_size() == 1


def test_burst_buckets_are_declared_in_manifest():
    """Cross-plane check: the fused burst is a registered entrypoint in
    the committed trace census (zero NEW trace keys is enforced by
    ``dynamo-tpu lint --trace``; here we pin that the entrypoint and its
    num_steps axis exist at all, so a future regression can't silently
    drop it from the census)."""
    from dynamo_tpu.analysis.tracecheck import DEFAULT_MANIFEST_PATH

    doc = json.loads(DEFAULT_MANIFEST_PATH.read_text())
    eps = doc["entrypoints"]
    assert "engine.unified_burst[tiny-llama]" in eps
    axes = eps["engine.unified_burst[tiny-llama]"]["axes"]
    assert axes["num_steps"] == [8]
    assert set(axes["r_pad"]) & {1, 2}, axes["r_pad"]
