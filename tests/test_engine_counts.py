"""What an engine counts is declared once (``obs/metric_names.py``
``ENGINE_COUNTS``) and stored once (``engine/counters.py`` ``EngineCounts``):
the table is the whole of ``EngineCore.metrics()``'s own keys and of the
engine's unlabelled names on ``/metrics``, ``/metrics`` is the sum over the
process's engines and never falls, a model names the columns of its
``moe_counts``, and the benchmark's per-layer metrics read keys that exist.
"""

import gc
import json
import threading
from pathlib import Path

import jax
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine import counters as engine_counters
from dynamo_tpu.engine.counters import EngineCounts, engine_totals, track_engine
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.http.metrics import Metrics
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.obs.metric_names import ENGINE_COUNTS, SCHEMA, EngineMetric

ROOT = Path(__file__).resolve().parents[1]

# what metrics() says of the scheduler and the block manager as they stand,
# and the step timeline's headline: read live, not counted
LIVE_GAUGES = {"request_active_slots", "request_total_slots",
               "kv_active_blocks", "kv_total_blocks", "num_requests_waiting",
               "kv_usage_perc", "host_gap_ms_per_turn"}
# the unlabelled engine names llm/http/metrics.py still renders by hand
HAND_RENDERED = {
    EngineMetric.PERSIST_HITS_TOTAL, EngineMetric.PERSIST_MISSES_TOTAL,
    EngineMetric.PERSIST_RESTORED_TOKENS_TOTAL,
    EngineMetric.PERSIST_SPILL_BYTES_TOTAL,
    EngineMetric.PERSIST_RESIDENT_BYTES, EngineMetric.STEPS_TOTAL,
    EngineMetric.BUSY_STEPS_TOTAL, EngineMetric.STEP_WALL_SECONDS_TOTAL,
    EngineMetric.HOST_GAP_MS_PER_TURN, EngineMetric.LAUNCHES_TOTAL,
    EngineMetric.STARVED_LAUNCHES_TOTAL}


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _isolated():
    engine_counters.reset()
    yield
    engine_counters.reset()


def make_core(tiny, **kw):
    model, params = tiny
    conf = dict(max_batch_size=4, max_model_len=128, block_size=8,
                num_blocks=48, prefill_chunk_tokens=32)
    conf.update(kw)
    return EngineCore(model, params, EngineConfig(**conf), eos_token_ids=[])


def serve(core, name: str, prompt_len: int, max_tokens: int) -> None:
    core.submit(EngineRequest(
        request_id=name, prompt=list(range(3, 3 + prompt_len)),
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        emit=lambda o: None))
    while core.step():
        pass


def rendered() -> dict:
    """name -> value of the unlabelled ``dynamo_tpu_engine_*`` samples."""
    out = {}
    for line in Metrics().render().splitlines():
        name, _, value = line.partition(" ")
        if name.startswith("dynamo_tpu_engine_") and "{" not in name:
            out[name] = float(value)
    return out


# ------------------------------------------ (a) the table is the surface
def test_the_table_is_metrics_own_keys_and_the_engines_names(tiny):
    core = make_core(tiny, num_host_blocks=8)
    serve(core, "a", 20, 4)
    m = core.metrics()
    own = {k for k, v in m.items() if isinstance(v, (int, float))} \
        - LIVE_GAUGES - set(core.host_pool.stats())
    assert own == {e.key for e in ENGINE_COUNTS if e.key}
    assert set(rendered()) - HAND_RENDERED \
        == {e.name for e in ENGINE_COUNTS if e.name}
    core.close()


def test_an_entry_is_well_formed_and_the_registry_follows_it():
    stored = set(EngineCounts.__slots__)
    for e in ENGINE_COUNTS:
        assert e.name or e.key, e
        assert e.kind in ("counter", "gauge") and e.help
        if e.name:
            assert e.name.startswith("dynamo_tpu_engine_")
            assert SCHEMA[e.name] == (e.kind, ())
            const = e.name[len("dynamo_tpu_engine_"):].upper()
            assert getattr(EngineMetric, const) == e.name
            # counters end in _total, gauges do not (metcheck's MT004)
            assert e.name.endswith("_total") == (e.kind == "counter")
        if e.ratio:
            assert e.kind == "gauge" and set(e.ratio) <= stored
        else:
            assert e.attr in stored
    names = [e.name for e in ENGINE_COUNTS if e.name]
    keys = [e.key for e in ENGINE_COUNTS if e.key]
    assert len(set(names)) == len(names) and len(set(keys)) == len(keys)
    assert isinstance(EngineCounts().first_token_seconds_total, float)


def test_prompt_tokens_computed_stays_an_int_attribute(tiny):
    """cellbench/run.py reads it with ``isinstance(..., int)``."""
    core = make_core(tiny)
    serve(core, "a", 20, 2)
    assert type(core.prompt_tokens_computed) is int
    assert core.prompt_tokens_computed == 20
    core.close()


# --------------------------- (b) /metrics is the sum, and it never falls
def test_two_engines_sum_on_the_render_and_closing_one_lowers_nothing(tiny):
    one, two = make_core(tiny), make_core(tiny, max_batch_size=2)
    serve(one, "a", 20, 6)
    serve(two, "b", 40, 3)
    serve(two, "c", 12, 5)
    m1, m2 = one.metrics(), two.metrics()
    assert m1["requests_finished_total"] == 1
    assert m2["requests_finished_total"] == 2
    text = rendered()
    for e in ENGINE_COUNTS:
        if e.name and e.key and e.kind == "counter":
            # (the render rounds to six places)
            assert text[e.name] == pytest.approx(
                m1[e.key] + m2[e.key], abs=1e-6), e
    # a ratio is taken over the summed operands, not averaged
    dispatches = m1["prefill_dispatches_total"] + m2["prefill_dispatches_total"]
    assert text[EngineMetric.PREFILL_BATCH_OCCUPANCY] == pytest.approx(
        (one.counts.prefill_rows_dispatched
         + two.counts.prefill_rows_dispatched) / dispatches)
    # the shape gauges are those of the engine built last
    assert text[EngineMetric.MESH_DEVICES] == 1

    one.close()
    one.close()                     # idempotent: folded in once
    assert rendered() == text
    assert one.metrics() == m1      # its own view stays
    del two                         # collected unclosed: folded in too
    gc.collect()
    assert rendered() == text
    three = make_core(tiny)
    serve(three, "d", 9, 2)
    after = rendered()
    assert all(after[e.name] >= text[e.name] for e in ENGINE_COUNTS
               if e.name and e.kind == "counter")
    assert after[EngineMetric.REQUESTS_FINISHED_TOTAL] == 4
    three.close()

    engine_counters.reset()
    fresh = rendered()
    assert fresh[EngineMetric.REQUESTS_FINISHED_TOTAL] == 0
    assert fresh[EngineMetric.MESH_TP] == fresh[EngineMetric.PREFIX_REUSE] == 1


def test_the_total_never_falls_while_engines_come_and_go():
    """Stores counted into, tracked and retired on four threads while a
    fifth sums: a count is in the live list or in the retired total, never
    in neither, so the sum only grows (and ends at what was counted)."""

    class Owner:
        pass

    stop, seen, rounds = threading.Event(), [], 200

    def churn():
        for _ in range(rounds):
            owner, counts = Owner(), EngineCounts()
            retire = track_engine(owner, counts)
            counts.requests_finished_total += 1
            retire()

    def watch():
        while not stop.is_set():
            seen.append(engine_totals().requests_finished_total)

    watcher = threading.Thread(target=watch)
    workers = [threading.Thread(target=churn) for _ in range(4)]
    watcher.start()
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=60)
    stop.set()
    watcher.join(timeout=60)
    assert not watcher.is_alive() and not any(t.is_alive() for t in workers)
    assert seen == sorted(seen)
    assert engine_totals().requests_finished_total == 4 * rounds


# ------------------- (c) a model names the columns of its ``moe_counts``
def _glm():
    from test_mistral4_mla import BS, NB, build

    model, _ = build()
    return model, model.init_kv_cache(NB, BS)


def _hybrid():
    from hybrid_linear_tiny import BS, NB, SLOTS, build

    model, _ = build()
    return model, model.init_kv_cache(NB, BS, slots=SLOTS)


@pytest.mark.parametrize("family", [_glm, _hybrid])
def test_a_model_names_every_column_of_its_device_counts(family):
    model, cache = family()
    keys = type(model).moe_count_keys
    assert len(keys) == cache["moe_counts"].shape[-1]
    declared = {e.key for e in ENGINE_COUNTS
                if e.kind == "counter" and e.ratio is None}
    assert len(set(keys)) == len(keys) and set(keys) <= declared


# --------- (d) the benchmark's per-layer metrics read keys that are there
def test_every_core_key_a_layer_metric_names_is_produced():
    produced = {e.key for e in ENGINE_COUNTS if e.key} | LIVE_GAUGES \
        | {"prompt_tokens_computed"}
    named = set()
    for path in sorted((ROOT / "cellbench" / "layer_metrics").glob("*.json")):
        for value in json.loads(path.read_text()).get("args", {}).values():
            for v in value if isinstance(value, list) else [value]:
                if isinstance(v, str) and v.startswith("core."):
                    named.add(v[len("core."):])
    assert len(named) >= 20 and named <= produced, named - produced
