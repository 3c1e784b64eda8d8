"""The system under test, put together in this process exactly as
``dynamo-tpu run in=http out=tpu`` puts it together (cli._build_local_engine,
cli._cmd_run), with two stated departures: the weights are made on the device
from the seed instead of being read from a checkpoint, and the tokenizer is a
word-level one over the configuration's whole vocabulary (token i is the word
``w<i>``), so that a prompt is a list of ids and an answer's ids can be read
back from its text.
"""

from __future__ import annotations

import importlib
import json
import os
import time
from dataclasses import dataclass
from typing import Any


def resolve(dotted: str):
    """``package.module:Name`` -> the object."""
    mod, _, name = dotted.partition(":")
    return getattr(importlib.import_module(mod), name)


def model_config(config: dict):
    """The program's own configuration object from the file's published keys."""
    cls = resolve(config.get("config_class", "dynamo_tpu.models.config:ModelConfig"))
    return cls.from_hf_config(config, dtype=config.get("dtype", "bfloat16"))


def serve_flags(serve: dict) -> list[str]:
    """A configuration's ``serve`` block as the flags of ``dynamo-tpu run``."""
    flags: list[str] = []
    for key, value in serve.items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            flags.append(flag)
        elif value is not False and value is not None:
            flags += [flag, str(value)]
    return flags


def run_args(serve: dict):
    """The namespace ``dynamo-tpu run`` would parse from these flags."""
    from dynamo_tpu import cli

    args = cli._parser().parse_args(
        ["run", "in=http", "out=tpu", "--model-path", "seeded",
         *serve_flags(serve)])
    args.inp, args.out = "http", "tpu"      # as cli.main splits in=/out=
    return args


def engine_config(args):
    """EngineConfig from ``run``'s namespace, field for field as
    cli._build_local_engine builds it (a test pins the two together)."""
    from dynamo_tpu.engine import EngineConfig

    return EngineConfig(
        max_batch_size=args.max_batch_size,
        max_model_len=args.max_model_len,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        num_host_blocks=int(args.num_host_blocks or 0),
        kv_persist_dir=args.kv_persist_dir or None,
        kv_persist_max_bytes=int(args.kv_persist_max_bytes or 0),
        kv_persist_ttl_s=float(args.kv_persist_ttl or 0),
        cache_dtype="int8" if args.kv_cache_dtype == "int8" else None,
        spec_tokens=int(args.spec_tokens or 0),
        draft_num_blocks=int(args.spec_draft_num_blocks or 0),
        sp_prefill_threshold=int(args.sp_prefill_threshold or 0),
        prefill_chunk_tokens=int(args.prefill_chunk_tokens or 0),
        prefill_token_budget=int(args.prefill_token_budget or 0),
        unified_token_dispatch=bool(args.unified_token_dispatch),
        lookahead_dispatch=bool(args.lookahead_dispatch),
        profile_dir=args.profile_dir or None,
        profile_steps=int(args.profile_steps or 8),
    )


def seed_key(seed: int):
    """A PRNG key from any whole number: the driver's seeds pass 2**31."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_params(model, seed: int, mesh):
    """The weights, on the device, from the seed, in one jitted call, in the
    type they are served in.  Under a mesh they come out in the engine's own
    parameter shardings, so no chip ever holds the whole model."""
    import jax

    key = seed_key(seed)
    if mesh is None:
        return model.init_params(key)
    from jax.sharding import NamedSharding, PartitionSpec

    from dynamo_tpu.models.quant import align_specs, prune_specs

    shapes = jax.eval_shape(model.init_params, key)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        align_specs(shapes, prune_specs(shapes, model.partition_specs(), mesh)),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    return jax.jit(model.init_params, out_shardings=shardings)(key)


def write_tokenizer(vocab_size: int, directory: str) -> str:
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = {"<unk>": 0, **{f"w{i}": i for i in range(1, vocab_size)}}
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    path = os.path.join(directory, "tokenizer.json")
    tok.save(path)
    return path


@dataclass
class Served:
    name: str
    model: Any
    core: Any
    engine: Any
    service: Any
    url: str
    vocab_size: int
    engine_config: Any
    split: dict             # seconds of set-up by part

    async def stop(self) -> None:
        await self.service.stop()
        self.engine.shutdown()


async def start(config: dict, seed: int, workdir: str) -> Served:
    """Model -> weights -> EngineCore -> AsyncLLMEngine -> serving pipeline
    -> HttpService on a free loopback port."""
    import jax

    from dynamo_tpu.engine import AsyncLLMEngine, EngineCore
    from dynamo_tpu.llm.engines import build_serving_pipeline
    from dynamo_tpu.llm.http import HttpService, ModelManager
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.runtime import serde
    from dynamo_tpu.utils.mesh import MESH_AXES, build_mesh

    split = {}
    t = time.monotonic()
    serde.register_llm_types()
    mcfg = model_config(config)
    model = resolve(config["model_class"])(mcfg)
    args = run_args(config["serve"])
    ecfg = engine_config(args)
    mesh = None
    if args.tp * args.dp > 1:
        mesh = build_mesh((args.dp, args.tp), MESH_AXES)
    params = make_params(model, seed, mesh)
    jax.block_until_ready(params)
    split["weights_s"] = time.monotonic() - t

    t = time.monotonic()
    core = EngineCore(model, params, ecfg, mesh=mesh, eos_token_ids=[])
    del params
    engine = AsyncLLMEngine(core).start()
    name = config.get("served_name", "cell")
    card = ModelDeploymentCard(
        name=name, tokenizer_path=write_tokenizer(mcfg.vocab_size, workdir),
        context_length=ecfg.max_model_len)
    manager = ModelManager()
    manager.add_model(name, build_serving_pipeline(engine, card), card)
    service = HttpService(manager, port=0)
    await service.start()
    split["engine_s"] = time.monotonic() - t
    print("# attention: " + json.dumps({
        phase: f"{impl} ({why})"
        for phase, (impl, why) in core.attention_impls().items()}), flush=True)
    return Served(
        name=name, model=model, core=core, engine=engine, service=service,
        url=f"http://127.0.0.1:{service.port}", vocab_size=mcfg.vocab_size,
        engine_config=ecfg, split=split)
