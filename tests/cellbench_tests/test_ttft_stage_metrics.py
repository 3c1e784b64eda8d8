"""The nine per-layer metrics of PR 43 (what a first token waits for): each
has its file, names a reader that exists, and reads a value from a pair of
edges recorded off the program's own surfaces — a tiny engine's ``metrics()``,
the step timeline's ``snapshot()`` and the front end's ``/metrics`` text,
under the dotted names ``cellbench/run.py::snapshot`` gives them — and reads
nothing, without raising, from a program that lacks the counters."""

import json

import pytest

from cellbench import run, spec
from roots import REPO

STAGE = ["engine.turn_wait_ms", "engine.prefill_ms",
         "sched.prefill_ready_per_dispatch", "http.pre_submit_ms",
         "http.emit_lag_ms", "step.decode_launch_ms", "step.decode_readback_ms",
         "step.prefill_launch_ms", "step.prefill_readback_ms"]
MOVES = {"http.emit_lag_ms": "itl_p95_ms", "step.decode_launch_ms": "itl_p95_ms",
         "step.decode_readback_ms": "itl_p95_ms"}


def read(name, ctx):
    desc = spec.load_layer_metric(REPO, name)
    return spec.load_module(REPO, "readers", desc["reader"]).read(
        ctx, desc.get("args", {}))


@pytest.fixture(scope="module")
def edges():
    """Before: one request served.  After: three more, two of them admitted
    in one turn, prompts in chunks of 16; two requests seen by the front
    end's histograms."""
    import jax
    import numpy as np

    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.obs.timeline import step_timeline

    model = LlamaModel(ModelConfig.tiny())
    core = EngineCore(model, model.init_params(jax.random.PRNGKey(0)),
                      EngineConfig(max_batch_size=4, max_model_len=128,
                                   block_size=8, num_blocks=64,
                                   prefill_buckets=[16, 32, 64, 128],
                                   prefill_chunk_tokens=16))
    http = Metrics()

    def serve(*lens):
        for i, n in enumerate(lens):
            prompt = np.random.RandomState(n + i).randint(1, 200, size=n)
            core.submit(EngineRequest(
                f"r{n}-{i}", [int(t) for t in prompt],
                SamplingOptions(temperature=0.0), StopConditions(max_tokens=4)))
        while core.step():
            pass

    def edge():
        out = {f"core.{k}": v for k, v in core.metrics().items()
               if isinstance(v, (int, float))}
        out.update({f"timeline.{k}": v
                    for k, v in step_timeline.snapshot().items()
                    if isinstance(v, (int, float))})
        out.update(run.parse_prom(http.render()))
        return out

    step_timeline.reset()
    serve(20)
    http.pre_submit["m"].observe(0.004)
    http.emit_lag["m"].observe(0.001)
    before = edge()
    serve(40, 40)
    serve(24)
    for v in (0.002, 0.006):
        http.pre_submit["m"].observe(v)
    for v in (0.0005, 0.0015, 0.004):
        http.emit_lag["m"].observe(v)
    return before, edge()


def test_the_nine_are_declared_last_in_the_issue_s_order_for_every_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-len(STAGE):]
    assert [m["name"] for m in tail] == STAGE
    assert not any("workloads" in m for m in tail)      # every cell owes them
    assert {m["name"]: m["moves"] for m in tail} == {
        n: MOVES.get(n, "ttft_mean_ms") for n in STAGE}


@pytest.mark.parametrize("name", STAGE)
def test_a_stage_metric_loads_and_reads_a_value_from_recorded_edges(name, edges):
    desc = spec.load_layer_metric(REPO, name)
    assert desc["name"] == name and desc["note"]
    assert desc["reader"] in ("counter_ratio", "prom_hist_mean")    # none new
    assert hasattr(spec.load_module(REPO, "readers", desc["reader"]), "read")
    value = read(name, {"edges": edges, "root": REPO})
    assert isinstance(value, float) and value >= 0.0
    if name != "step.prefill_readback_ms":      # a serial CPU engine may read 0
        assert value > 0.0
    # a program without the counter (the parent of PR 43): nothing, no raise
    assert read(name, {"edges": ({}, {}), "root": REPO}) is None


def test_the_stages_read_back_what_the_edges_hold(edges):
    before, after = edges
    ctx = {"edges": edges, "root": REPO}
    d = lambda k: after[k] - before[k]
    assert d("core.first_tokens_total") == 3
    # the three stages are the engine's TTFT: the queue wait is the rest
    rest = read("engine.ttft_ms", ctx) - read("engine.turn_wait_ms", ctx) \
        - read("engine.prefill_ms", ctx)
    assert 0.0 <= rest < read("engine.ttft_ms", ctx)
    # 40 + 40 in one turn: 3 dispatches with two ready, 3 with one; 24: 2
    assert d("core.prefill_dispatches_total") == 8
    assert read("sched.prefill_ready_per_dispatch", ctx) == pytest.approx(11 / 8)
    assert read("http.pre_submit_ms", ctx) == pytest.approx(4.0)
    assert read("http.emit_lag_ms", ctx) == pytest.approx(2.0)
    for cls in ("decode", "prefill"):
        launch = read(f"step.{cls}_launch_ms", ctx)
        readback = read(f"step.{cls}_readback_ms", ctx)
        assert launch + readback <= read(f"step.{cls}_wall_ms", ctx)
        n = d(f"timeline.{cls}_steps_total")
        assert launch * n == pytest.approx(
            d(f"timeline.{cls}_launch_seconds_total") * 1e3)
