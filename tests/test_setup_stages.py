"""benchmarks/setup_stages.py books jax's events and the engine's prefill
programs under the stage of the set-up they fall in (the run itself is the
benchmark's: tests/cellbench_tests rehearse that)."""

import asyncio
import importlib.util
import types
from pathlib import Path

import jax
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel


@pytest.fixture(scope="module")
def stages_module():
    path = Path(__file__).parents[1] / "benchmarks" / "setup_stages.py"
    spec = importlib.util.spec_from_file_location("_setup_stages", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_events_and_marks_fall_under_the_stage_that_runs(stages_module):
    stages = stages_module.Stages()
    stages.on_event("/jax/core/compile/backend_compile_duration", 0.5)

    async def warm_up(served, n):
        stages.on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25)
        stages.on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.5)
        return n + 1

    run = stages.staged("warm_up", warm_up, after=lambda served, n: served)
    assert asyncio.run(run("the server", 1)) == 2
    stages.on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    out = stages.report()
    assert out["events"] == {
        "start": {"backend_compile_duration": [1, 0.5]},
        "warm_up": {"jaxpr_to_mlir_module_duration": [2, 0.75]},
        "between": {"cache_retrieval_time_sec": [1, 0.125]}}
    assert out["marks"]["warm_up_programs"] == "the server"
    assert out["marks"]["warm_up_s"] >= 0.0


def test_prefill_programs_reads_the_jit_caches(stages_module):
    model = LlamaModel(ModelConfig.tiny())
    core = EngineCore(
        model, model.init_params(jax.random.PRNGKey(0)),
        EngineConfig(max_batch_size=2, max_model_len=64, block_size=8,
                     num_blocks=16, prefill_buckets=[16, 32, 64],
                     prefill_chunk_tokens=16), eos_token_ids=[])
    core.submit(EngineRequest(
        "r", list(range(3, 43)), SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=2, ignore_eos=True), lambda out: None))
    while core.step():
        pass
    got = stages_module.prefill_programs(types.SimpleNamespace(core=core))
    # 40 tokens in chunks of 16: behind 0, 2 and 4 cached blocks (the XLA
    # form keys a program by each), the last chunk in the 16-token bucket too
    assert got["_step_fn"] == got["prefill_programs_total"] == 3
    assert got["prefill_dispatches_total"] == 3
    assert got["_ragged_fn"] == got["_unified_fn"] == 0
