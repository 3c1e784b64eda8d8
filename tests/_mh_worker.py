"""Worker process for the multi-host tests: joins the mesh via the
coordinator rendezvous, runs a sharded engine step, prints its tokens.

Launched by tests/test_multihost.py as `python tests/_mh_worker.py` with
DYN_MH_* env vars; NOT a pytest module (leading underscore keeps
collection away)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.utils import force_cpu_devices

LOCAL_DEVICES = int(os.environ.get("DYN_MH_LOCAL_DEVICES", "4"))
force_cpu_devices(LOCAL_DEVICES)

from dynamo_tpu.runtime.multihost import bootstrap, global_mesh, spec_from_env


def main() -> None:
    spec = spec_from_env()
    bootstrap(spec, timeout=60.0)

    import jax

    assert len(jax.devices()) == LOCAL_DEVICES * spec.num_processes, jax.devices()
    mesh = global_mesh((spec.num_processes, LOCAL_DEVICES), ("data", "model"))

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.core import EngineCore
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    # kv heads shard the cache over the "model" axis — match its size
    cfg = ModelConfig.tiny(
        num_heads=max(4, 2 * LOCAL_DEVICES), num_kv_heads=LOCAL_DEVICES
    )
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    if os.environ.get("DYN_MH_QUANT"):
        params = model.quantize_params(params)
    ecfg = EngineConfig(max_batch_size=2, max_model_len=64, block_size=16,
                        num_blocks=16)
    engine = EngineCore(model, params, ecfg, mesh=mesh, eos_token_ids=[])

    toks: list[int] = []
    engine.submit(EngineRequest(
        request_id="mh", prompt=[3, 1, 4, 1, 5, 9, 2, 6],
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=6, ignore_eos=True),
        emit=lambda out: toks.extend(out.token_ids),
    ))
    for _ in range(64):
        if not engine.step():
            break
    print(f"TOKENS rank={spec.process_id} {toks}", flush=True)
    assert len(toks) == 6


if __name__ == "__main__":
    main()
