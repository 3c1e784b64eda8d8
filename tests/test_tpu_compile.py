"""The serving kernels compile for the real chip at real widths.

Nothing here executes: the TPU compiler is installed without a TPU, and
compiles for a *described* v5e (``on-chip-measurement`` guide §2,
rehearsal 3).  That catches what interpret mode cannot — Mosaic layout
refusals (the head_dim-64 prefill regroup), VMEM overflows, kernels GSPMD
cannot partition — at no chip time.  Every case goes through the dispatch
in ops/paged_attention.py with the backend gate steered to "tpu", so a
phase the static rule calls ``pallas`` is shown to compile as one.
Results and times come only from ``chip_smoke.py`` on the chip.
"""

import contextlib
import functools
import importlib
import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.kv_quant import QuantKvCache, scale_tile

# the package re-exports a function of the same name over the module
pa = importlib.import_module("dynamo_tpu.ops.paged_attention")

# published attention widths (heads, kv heads, head_dim) and the serving
# geometry of the one old chip record: block 32, batch 64, 2048 context
GEOMETRIES = {
    "llama-3.2-1b": dict(h=32, hk=8, d=64, matmul=(64, 2048, 8192)),
    "llama-3-8b": dict(h=32, hk=8, d=128, matmul=(64, 4096, 14336)),
    # plain multi-head attention: Hk*D = 2,048 lanes, twice any other's
    "ouro-2.6b": dict(h=16, hk=16, d=128, matmul=(64, 2048, 5632)),
}
BS, N_BLOCKS, M, B = 32, 512, 64, 64
PREFILL_S, RAGGED_T, RAGGED_ROWS, MQ_S = 512, 1024, 8, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip (the next run warns and
    recompiles) — keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def tpu_gate(monkeypatch):
    """The dispatch asks jax.default_backend(), which is the CPU here:
    steer it in the test, not through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# how the engine shards the operands under a mesh (models/llama.py);
# a one-chip ``sds`` ignores the spec
_CACHE_SPEC = P(None, None, None, None, "model")
_HEADS = P(None, None, "model", None)


def _cache(sds, hk, d, quant):
    data = (1, N_BLOCKS, 2, BS, hk * d)
    if not quant:
        return sds(data, jnp.bfloat16, _CACHE_SPEC)
    hp, sp = scale_tile(hk, BS)
    return QuantKvCache(sds(data, jnp.int8, _CACHE_SPEC),
                        sds((1, N_BLOCKS, 2, hp, sp), jnp.float32))


def _phase_call(phase, sds, h, hk, d, quant):
    """(fn, abstract args, index of the cache) for one dispatch site at
    serving geometry; ``sds(shape, dtype, spec=P())`` makes the args."""
    i32, bf16 = jnp.int32, jnp.bfloat16
    cache = _cache(sds, hk, d, quant)
    sm = d ** -0.5
    if phase in ("decode", "mq"):
        s = 1 if phase == "decode" else MQ_S
        fn = functools.partial(pa.paged_attention_layer, sm_scale=sm)
        return fn, (sds((B, s, h, d), bf16, _HEADS), cache, sds((), i32),
                    sds((B, M), i32), sds((B,), i32), sds((B, s), i32)), 1
    if phase == "prefill":
        fn = functools.partial(pa.prefill_attention, prefix_blocks=1,
                               sm_scale=sm)
        return fn, (sds((1, PREFILL_S, h, d), bf16, _HEADS),
                    sds((1, PREFILL_S, hk, d), bf16, _HEADS),
                    sds((1, PREFILL_S, hk, d), bf16, _HEADS),
                    cache, sds((), i32), sds((1, M), i32),
                    sds((1,), i32), sds((1,), i32)), 3
    r = RAGGED_ROWS
    fn = functools.partial(pa.ragged_prefill_attention, prefix_blocks=1,
                           sm_scale=sm)
    return fn, (sds((1, RAGGED_T, h, d), bf16, _HEADS),
                sds((1, RAGGED_T, hk, d), bf16, _HEADS),
                sds((1, RAGGED_T, hk, d), bf16, _HEADS),
                cache, sds((), i32), sds((r, M), i32), sds((r,), i32),
                sds((r,), i32), sds((r,), i32), sds((1, RAGGED_T), i32)), 3


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8-kv"])
@pytest.mark.parametrize("model", sorted(GEOMETRIES))
@pytest.mark.parametrize("phase", sorted(pa.ATTENTION_PHASES))
def test_attention_phase_compiles_on_one_chip(topo, tpu_gate, phase, model,
                                              quant):
    g = GEOMETRIES[model]
    impl, why = pa.attention_impl(
        phase, num_kv_heads=g["hk"], block_size=BS, quant=quant)
    assert impl == "pallas", why
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args, _ = _phase_call(phase, sds, g["h"], g["hk"], g["d"], quant)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("model", sorted(GEOMETRIES))
def test_int8_matmul_compiles_on_one_chip(topo, model):
    from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul

    m, k, n = GEOMETRIES[model]["matmul"]
    one_chip = SingleDeviceSharding(topo.devices[0])
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(int8_matmul).lower(
        sds((m, k), jnp.bfloat16), sds((k, n), jnp.int8),
        sds((n,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("phase", sorted(pa.ATTENTION_PHASES))
def test_attention_phase_compiles_under_tp4(topo, tpu_gate, phase):
    """--tp 4: Mosaic kernels cannot be partitioned by GSPMD, so the
    dispatch runs them per kv-head shard under shard_map when the trace
    has a mesh with model > 1 in scope.  Each device then holds a quarter
    of the cache."""
    g = GEOMETRIES["llama-3.2-1b"]
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    impl, why = pa.attention_impl(
        phase, num_kv_heads=g["hk"], block_size=BS, tp=4)
    assert impl == "pallas" and "shard_map" in why, why

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    fn, args, cache_at = _phase_call(
        phase, sds, g["h"], g["hk"], g["d"], False)

    def under_mesh(*a):  # what EngineCore's jit does around its impls
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*a)

    compiled = jax.jit(under_mesh).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    cache_bytes = np.prod(args[cache_at].shape) * 2  # bf16
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert cache_bytes / 4 <= per_device < cache_bytes / 4 * 1.25, (
        per_device, cache_bytes)


@pytest.mark.parametrize("tp", [1, 4])
def test_packed_decode_program_compiles(topo, tpu_gate, tp):
    """PR 55: a decode as the engine jits it — its small operands one int32
    buffer that the program takes apart, float32 rows out of their bits,
    its key read out of the key block — compiles for the chip as ONE
    program named for its impl, the buffer replicated under --tp 4."""
    from dynamo_tpu.engine import operands
    from dynamo_tpu.engine.core import KEY_BLOCK, multi_decode_step, packed

    if tp > 1:
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp),
                    ("data", "model"))
        place = lambda spec: NamedSharding(mesh, spec)
    else:
        mesh = None
        place = lambda spec: SingleDeviceSharding(topo.devices[0])
    _, cfg, model, params, cache, sds = _abstract_model(
        "mistral-7b.json", place, N_BLOCKS, num_hidden_layers=2)
    b = 32

    def _multi_impl(params, cache, *a, **kw):
        with (jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
              if mesh else contextlib.nullcontext()):
            return multi_decode_step(model, params, cache, *a,
                                     block_size=BS, **kw)

    i32 = lambda *shape: np.zeros(shape, np.int32)
    f32 = lambda *shape: np.zeros(shape, np.float32)
    bufs, layout = operands.pack((
        i32(),
        (i32(b), i32(b), i32(b, M), i32(b), i32(b), None, f32(b), i32(b),
         f32(b)),
        {"carry_rows": np.zeros(b, bool), "min_p": f32(b)}))
    assert len(bufs) == 1
    lowered = jax.jit(packed(_multi_impl), donate_argnums=(1,),
                      static_argnames="layout").lower(
        params, cache, sds((KEY_BLOCK, 2), jnp.uint32),
        tuple(sds(a.shape, a.dtype) for a in bufs), layout=layout,
        carry_tokens=sds((1, b)))
    assert "module @jit__multi_impl " in lowered.as_text()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo                 # the decode kernel
    # the program reads its key, it does not make it: no split of the
    # engine's key in two
    assert "tensor<2x2xui32>" not in lowered.as_text()
    # the one buffer is an operand of the entry computation as it is
    assert re.search(rf"s32\[{bufs[0].size}\]\S* parameter\(", hlo)
    # ... and the cache still aliases its output
    assert compiled.memory_analysis().alias_size_in_bytes > 0


# ---------------------------------------------------------------------------
# Qwen3-30B-A3B, the benchmark's MoE configuration, at depth 2: the expert
# weights are read where they lie.  ``lax.ragged_dot`` is a Mosaic custom
# call on the TPU and takes whole buffers, so a layer the scan slices out of
# the stacked [L, E, Dm, F] arrays is materialised: three copies of
# E·Dm·F·2 = 403 MB a layer, 62% of the cell's device time (ledger, PR 25).
# On one chip the three products are ``grouped_expert_matmul``
# (ops/pallas/grouped_matmul.py, PR 50: few rows an expert), which takes the
# stacked arrays as they are; under a mesh ``ragged_dot`` stays.
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(")
_PASS_THROUGH = ("parameter", "get-tuple-element", "bitcast")


def _unfused_instructions(hlo: str):
    """(name, dtype, dims, opcode) of each instruction of the optimised HLO
    that is not inside a fused computation, whose values live in registers."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    inside = ""
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            inside = head.group(1)
        m = _HLO_INSTR.match(line)
        if m and inside not in fused:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            yield m.group(1), m.group(2), dims, m.group(4)


def _largest_produced(hlo: str) -> tuple[int, str]:
    """(bytes, instruction) of the largest array an instruction of the
    optimised HLO writes to memory: not one that hands a buffer on, and
    not one inside a fused computation."""
    worst = (0, "")
    for name, dtype, dims, op in _unfused_instructions(hlo):
        if op in _PASS_THROUGH:
            continue
        bits = re.search(r"\d+", dtype)          # bf16, f32, s8; pred: none
        size = max(int(bits.group()) // 8, 1) if bits else 1
        worst = max(worst, (size * math.prod(dims), f"{name} = {op}"))
    return worst


def _grouped_matmul_calls(hlo: str) -> list[str]:
    """The custom calls of ops/pallas/grouped_matmul.py in an optimised HLO."""
    return [line for line in hlo.splitlines()
            if "custom-call(" in line and "grouped_expert_matmul" in line]


def _abstract_model(config_file: str, place, num_blocks=None, **overrides):
    """(file, cfg, model, params, cache, sds) of a benchmark configuration
    as shapes with shardings: ``place(spec)`` turns a PartitionSpec of
    models/llama.py into the sharding of the described device(s); the cache
    has the configuration's own ``num_blocks`` unless one is given."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench/configs", config_file)) as f:
        hf = dict(json.load(f), **overrides)

    def resolve(key, default):   # "module:Class", as cellbench/server.py
        module, name = hf.get(key, default).split(":")
        return getattr(importlib.import_module(module), name)

    cfg = resolve("config_class", "dynamo_tpu.models.config:ModelConfig"
                  ).from_hf_config(hf, dtype=hf["dtype"])
    model = resolve("model_class", "dynamo_tpu.models.llama:LlamaModel")(cfg)
    num_blocks = num_blocks or hf["serve"]["num_blocks"]

    def sds(shape, dtype=jnp.int32, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place(spec))

    def placed(shapes, specs):
        return jax.tree.map(lambda a, spec: sds(a.shape, a.dtype, spec),
                            shapes, specs)

    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    specs = (jax.tree.map(lambda a: P(), shapes)      # GLM: one chip, no specs
             if getattr(model, "private_cache_layout", False)
             else model.partition_specs())
    params = placed(shapes, specs)
    slots = ({"slots": hf["serve"]["max_batch_size"]}     # a state per slot
             if getattr(model, "recurrent_state", False) else {})
    cache = placed(
        jax.eval_shape(lambda: model.init_kv_cache(num_blocks, BS, **slots)),
        model.cache_spec())
    return hf, cfg, model, params, cache, sds


@pytest.mark.parametrize(
    "case", ["decode", "prefill", "engine-decode", "decode-tp2", "decode-tp4"])
def test_qwen3_moe_reads_expert_weights_in_place(topo, tpu_gate, case):
    from dynamo_tpu.engine.core import multi_decode_step
    from dynamo_tpu.models.llama import experts_in_place

    tp = {"decode-tp2": 2, "decode-tp4": 4}.get(case, 1)
    if tp > 1:   # --tp 4 is the 2x2 host as one "model" axis
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp),
                    ("data", "model"))
        place = lambda spec: NamedSharding(mesh, spec)
    else:
        mesh = None
        place = lambda spec: SingleDeviceSharding(topo.devices[0])
    _, cfg, model, params, cache, sds = _abstract_model(
        "qwen3-30b-a3b.json", place, N_BLOCKS, num_hidden_layers=2)
    expert_bytes = (cfg.num_experts * cfg.hidden_size
                    * cfg.intermediate_size * 2)
    b, s = (1, PREFILL_S) if case == "prefill" else (32, 1)

    if case == "engine-decode":   # the nested scan the served path runs
        def fn(params, cache, *a):
            return multi_decode_step(model, params, cache, *a,
                                     block_size=BS)
        args = (sds((b,)), sds((b,)), sds((b, M)), sds((b,)), sds((b,)),
                sds((2,), jnp.uint32), sds((b,), jnp.float32), sds((b,)),
                sds((b,), jnp.float32))
    else:
        def fn(params, cache, tokens, positions, tables, lens, slots):
            kw = dict(prefix_blocks=1) if case == "prefill" else {}
            with (jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
                  if mesh else contextlib.nullcontext()):
                return model.forward(params, tokens, positions, cache,
                                     tables, lens, slots, **kw)
        args = (sds((b, s)), sds((b, s)), sds((b, M)), sds((b,)),
                sds((b, s)))

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    if tp > 1:
        assert hlo.count("ragged-dot-none") >= 3
        assert "grouped_expert_matmul" not in hlo
    else:
        # 2 rows an expert (decode) and 32 (a 512-token chunk): the kernel
        # (gate and up in one call, down in another), its weight operands
        # the whole stacks [L·E, K, N] as they lie
        calls = _grouped_matmul_calls(hlo)
        assert len(calls) >= 2 and "ragged-dot" not in hlo
        stacks = {f"bf16[{2 * cfg.num_experts},{k},{n}]" for k, n in (
            (cfg.hidden_size, cfg.intermediate_size),
            (cfg.intermediate_size, cfg.hidden_size))}
        for line in calls:
            layouts = line[line.index("operand_layout_constraints"):]
            assert any(stack in layouts for stack in stacks), line
    # Per device, a layer's experts are expert_bytes / tp.  At tp 4 a shard
    # is F/4 = 192 wide, not a multiple of the 128 lanes, and the static
    # rule keeps the sliced form (copies of a layer's shard, 101 MB): the
    # in-place form there re-lays out both whole stacks every step (temp
    # 538 MB at this depth, growing with it), which the whole-layer bound
    # catches.
    in_place = experts_in_place(params["layers"], tp)
    assert in_place == (tp != 4)
    bound = expert_bytes // tp if in_place else expert_bytes
    size, instr = _largest_produced(hlo)
    assert size < bound, (instr, size)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < bound, temp


# ---------------------------------------------------------------------------
# Mistral-7B-v0.3 whole under --tp 4, the benchmark's four-chip configuration
# (PR 27): the two programs a step of `mistral-7b-tp4.chat-closed` runs, at
# the configuration's own batch and cache, for the 2x2 host.
_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
V5E_HBM = 15.75 * 2**30


def _collective_census(hlo: str) -> tuple[dict, dict]:
    """Collectives by kind: (inside the layer scan's body, outside it).  The
    scan is the one ``while`` of these programs; its body computation is the
    one that holds the attention kernel's custom call."""
    by_comp: dict[str, dict] = {}
    kernel_in, comp = set(), ""
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
        if "tpu_custom_call" in line:
            kernel_in.add(comp)
        m = _COLLECTIVE.search(line)
        if m:
            kinds = by_comp.setdefault(comp, {})
            kinds[m.group(1)] = kinds.get(m.group(1), 0) + 1
    assert len(kernel_in) == 1, kernel_in
    body = kernel_in.pop()
    outside: dict[str, int] = {}
    for c, kinds in by_comp.items():
        if c != body:
            for k, n in kinds.items():
                outside[k] = outside.get(k, 0) + n
    return by_comp.get(body, {}), outside


def _step_program(program, model, serve, sds, mesh=None, prefix_blocks=1):
    """(fn, args) of a step as the engine issues it at the configuration's
    own ``serve`` geometry — ``decode``: the whole batch, one token a row;
    ``prefill``: one chunk of one prompt with ``prefix_blocks`` blocks of
    it cached — for ``jax.jit(fn, donate_argnums=(1,)).lower(params,
    cache, *args)``."""
    from dynamo_tpu.engine.core import multi_decode_step, unified_step

    bs = serve["block_size"]
    assert bs == BS
    m = serve["max_model_len"] // bs
    f32, key = jnp.float32, sds((2,), jnp.uint32)
    under_mesh = (
        (lambda: jax.sharding.use_abstract_mesh(mesh.abstract_mesh))
        if mesh else contextlib.nullcontext)
    if program == "decode":
        b = serve["max_batch_size"]
        # the last decode's samples and the mask of the rows that take
        # their token from them (dispatch-ahead)
        args = (sds((b,)), sds((b,)), sds((b, m)), sds((b,)), sds((b,)), key,
                sds((b,), f32), sds((b,)), sds((b,), f32),
                sds((1, b)), sds((b,), jnp.bool_))

        def fn(params, cache, *a):
            with under_mesh():
                return multi_decode_step(
                    model, params, cache, *a[:-2], carry_tokens=a[-2],
                    carry_rows=a[-1], block_size=bs)
    else:
        s = serve["prefill_chunk_tokens"]
        args = (sds((1, s)), sds((1, s)), sds((1, m)), sds((1,)), sds((1, s)),
                sds((1,)), key, sds((1,), f32), sds((1,)), sds((1,), f32))

        def fn(params, cache, *a):
            with under_mesh():
                return unified_step(model, params, cache, *a,
                                    prefix_blocks=prefix_blocks)
    return fn, args


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mistral_7b_whole_compiles_under_tp4(topo, tpu_gate, program):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    hf, cfg, model, params, cache, sds = _abstract_model(
        "mistral-7b-tp4.json", lambda spec: NamedSharding(mesh, spec))
    serve = hf["serve"]
    assert hf["num_hidden_layers"] == 32 and hf["reduced"] == [] and serve["tp"] == 4
    # prefill: sixteen blocks of the prompt already cached
    fn, args = _step_program(program, model, serve, sds, mesh,
                             prefix_blocks=16)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # 3.56 GiB of weights and the 4 GiB cache shard, donated, and no second
    # copy of the cache among the temporaries (nor on one chip:
    # test_one_chip_programs_keep_one_cache, ROADMAP S7)
    assert 7.5 * 2**30 < per_device < V5E_HBM, per_device
    assert mem.temp_size_in_bytes < 2**30, mem.temp_size_in_bytes
    # a layer all-reduces twice (after wo and after w_down) and does nothing
    # else across chips; the vocabulary-sharded head gathers its candidates
    # and their ids and all-reduces the log-sum-exp.  One more collective a
    # layer is 32 more a step: a test failure here, not a slow cell there.
    in_layer, outside = _collective_census(compiled.as_text())
    assert in_layer == {"all-reduce": 2}, in_layer
    assert outside == {"all-gather": 2, "all-reduce": 2}, outside


# ---------------------------------------------------------------------------
# The layer scan re-lays no projection weight (PR 38).  XLA folds a reshape
# that follows a dot into the dot; with the head reshape of q and k folded in,
# the TPU compiler asked for ``wq``/``wk`` as [H, D, Dm] and the scan's body
# transposed all of both every layer of every step (`copy.21` + `copy.23`,
# 40 MB a Mistral layer; GLM's ``q_b`` 67 MB, its indexer's ``idx_wq_b``
# 16 MB).  ``models/llama.py::split_heads`` keeps the reshape out of the dot.
# Two layers show what every layer does; GLM's own five are three kinds.
_TWO_LAYERS = {"num_hidden_layers": 2}
_SCAN_PROGRAMS = {       # configuration file, program, chips, overrides
    "mistral-7b-decode": ("mistral-7b.json", "decode", 1, _TWO_LAYERS),
    "mistral-7b-prefill": ("mistral-7b.json", "prefill", 1, _TWO_LAYERS),
    "qwen3-30b-a3b-decode": ("qwen3-30b-a3b.json", "decode", 1, _TWO_LAYERS),
    "mistral-7b-tp4-decode": ("mistral-7b-tp4.json", "decode", 4, _TWO_LAYERS),
    "glm-5.2-ep16-decode": ("glm-5.2-ep16.json", "decode", 1, {}),
    "mistral-small-4-ep8-decode": ("mistral-small-4-ep8.json", "decode", 1,
                                   _TWO_LAYERS),
    "ouro-2.6b-decode": ("ouro-2.6b.json", "decode", 1, _TWO_LAYERS),
    "ouro-2.6b-prefill": ("ouro-2.6b.json", "prefill", 1, _TWO_LAYERS),
    # not a cell: the looped model sharded as any LlamaModel (docs/looped_layers.md)
    "ouro-2.6b-tp4-decode": ("ouro-2.6b.json", "decode", 4, _TWO_LAYERS),
}
# GLM's ``kv_b`` is still transposed once a layer ([1,512,28672], 29 MB):
# its two einsums are batched over the head, which lies in the middle of
# the stored [r, H·(nope+v)], and a batched dot wants the batch dimension
# outermost — only another stored layout would spare it (PERF.md, PR 38).
_STILL_RELAID = {"kv_b"}
_HLO_DTYPES = {"bf16": "bfloat16", "f32": "float32"}


def _layer_slices(params) -> dict[tuple, set[str]]:
    """{(dtype, sorted dims): names} of one layer's slice, on one device, of
    every stacked weight of 1 MiB and more."""
    out: dict[tuple, set[str]] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path[-1:]).strip("[]'")
        if leaf.ndim < 3 or name in _STILL_RELAID:
            continue
        dims = leaf.sharding.shard_shape(leaf.shape)[1:]
        if math.prod(dims) * leaf.dtype.itemsize >= 2**20:
            out.setdefault((str(leaf.dtype), tuple(sorted(dims))),
                           set()).add(name)
    return out


_SCAN_HLO: dict[str, tuple] = {}


def _scan_program(topo, case):
    """(optimised HLO, params) of one of ``_SCAN_PROGRAMS`` compiled for the
    described chip(s); compiled once a module."""
    if case not in _SCAN_HLO:
        config_file, program, chips, overrides = _SCAN_PROGRAMS[case]
        if chips > 1:
            mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                        ("data", "model"))
            place = lambda spec: NamedSharding(mesh, spec)
        else:
            mesh = None
            place = lambda spec: SingleDeviceSharding(topo.devices[0])
        hf, cfg, model, params, cache, sds = _abstract_model(
            config_file, place, N_BLOCKS, **overrides)
        fn, args = _step_program(program, model, hf["serve"], sds, mesh)
        hlo = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile().as_text()
        _SCAN_HLO[case] = (hlo, params)
    return _SCAN_HLO[case]


@pytest.mark.parametrize("case", sorted(_SCAN_PROGRAMS))
def test_layer_scan_copies_no_projection_weight(topo, tpu_gate, case):
    hlo, params = _scan_program(topo, case)
    assert "tpu_custom_call" in hlo and " while(" in hlo

    weights = _layer_slices(params)
    assert sum(map(len, weights.values())) >= 3, weights
    relaid = []
    for name, dtype, dims, op in _unfused_instructions(hlo):
        if op not in ("copy", "transpose"):
            continue
        # by shape, not by size: a 512-row prefill copies 4 MB of
        # activations, more than Qwen3's whole wk
        shape = tuple(sorted(d for d in dims if d != 1))
        names = weights.get((_HLO_DTYPES.get(dtype), shape))
        if names:
            relaid.append(f"{name} = {dtype}{list(dims)} {op}: a layer of "
                          + "/".join(sorted(names)))
    assert not relaid, relaid


# ---------------------------------------------------------------------------
# A decode step's rows go through the layers grouped by context length (PR 40:
# the decode kernel loops to the longest row of each group of G).  The order
# is two sorts of the batch's lengths, made once a step at the model's inputs:
# XLA moves no sort out of a loop, so one made beside the kernel would run in
# every layer (192 times a step in ouro-2.6b).
_SORT = re.compile(r" sort\(")


@pytest.mark.parametrize("case", [
    "mistral-7b-decode", "ouro-2.6b-decode", "mistral-7b-tp4-decode"])
def test_decode_rows_are_ordered_outside_the_layer_scan(topo, tpu_gate, case):
    hlo, _ = _scan_program(topo, case)
    sorts: dict[str, int] = {}
    kernel_in, comp = set(), ""
    for line in hlo.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
        if "tpu_custom_call" in line:
            kernel_in.add(comp)
        if _SORT.search(line):
            sorts[comp] = sorts.get(comp, 0) + 1
    (body,) = kernel_in          # the layer scan's body holds the kernel
    assert body not in sorts, sorts
    # the order and its inverse (and the sampler's top-k) are in the program
    assert sum(sorts.values()) >= 2, sorts


# ---------------------------------------------------------------------------
# A looped decoder whole on one chip (PR 39): 48 layers run 4 times, 192
# cache layers of 2,048 lanes.  The decode kernel's tiling follows the
# geometry (at G 8, C 4 its K/V scratch alone is 16 MiB, the compiler's
# scoped limit), and neither program keeps a second copy of the cache:
# 4.97 GiB of weights and the configuration's own cache, donated, are all.
def test_decode_tiling_follows_the_geometry():
    from dynamo_tpu.ops.pallas import registry

    # rows a sequence, Hk*D lanes of the benchmark's decode kernels: a
    # row-chunk of 512 KiB of K/V whatever the lanes (PR 47), and the
    # sequences a batched update takes
    accepted = {
        "mistral-7b": (32, 1024, (8, 4), 4), "llama-3-8b": (32, 1024, (8, 4), 4),
        "qwen3-30b-a3b": (32, 512, (8, 8), 4),
        "mistral-7b-tp4 (a shard)": (8, 256, (8, 16), 4),
        "zaya1-8b (one chip, whole)": (8, 256, (8, 16), 4),
        "ouro-2.6b": (16, 2048, (8, 2), 2)}
    for name, (rows, hkd, tiling, r) in accepted.items():
        g, c = registry.decode_tiling(rows, hkd, BS)
        assert (g, c) == tiling, name
        assert c * 2 * BS * hkd * 2 == registry.DECODE_CHUNK_BYTES, name
        assert registry.decode_seqs_per_update(g, c, BS) == r, name
        assert registry.decode_vmem_bytes(g, c, rows, hkd, BS) \
            <= registry.SCOPED_VMEM_BYTES, name
    # ... and a prefill grid step takes 128 tokens of ZAYA's 8 heads
    assert registry.prefill_rows_per_chunk(8) == 128
    # an int8 block brings a scale tile: two copies and two semaphores a site
    g, c = registry.decode_tiling(8, 256, BS, cache_bytes=1)
    assert 2 * g * c <= registry.DECODE_MAX_DMA_SITES and c == 16
    # narrow toy rows: never more blocks a chunk than the cap, nor sites
    g, c = registry.decode_tiling(4, 32, 8, cache_bytes=4, q_bytes=4)
    assert c == registry.DECODE_MAX_BLOCKS_PER_CHUNK
    assert g * c <= registry.DECODE_MAX_DMA_SITES
    # at 2,048 lanes G 8, C 4 would not fit: the K/V scratch the issue of
    # PR 39 counted, (2, G, C, 2, Bs, Hk*D) in bf16, is the scoped limit
    assert 2 * 8 * 4 * 2 * BS * 2048 * 2 == registry.SCOPED_VMEM_BYTES
    assert registry.decode_vmem_bytes(8, 4, 16, 2048, BS) \
        > registry.SCOPED_VMEM_BYTES


def _probe_geoms():
    from benchmarks.probe_kernels import DECODE_GEOMS

    return sorted(DECODE_GEOMS)


@pytest.mark.parametrize("geom", _probe_geoms())
def test_decode_kernel_fits_scoped_vmem_at_every_probe_geometry(topo, geom):
    """The kernel alone at the four cell geometries of the chip sweep
    (``benchmarks/probe_kernels.py lengths``), with the tiling the rule
    gives and a table of 128 blocks: the chip's compiler refuses a kernel
    whose scratch and temporaries pass its scoped VMEM."""
    from benchmarks.probe_kernels import DECODE_GEOMS
    from dynamo_tpu.ops.pallas import registry
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    rows, h, hk = DECODE_GEOMS[geom]
    d, m = 128, 128
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    g, c = registry.decode_tiling(h, hk * d, BS)
    assert registry.decode_vmem_bytes(g, c, h, hk * d, BS) \
        <= registry.SCOPED_VMEM_BYTES
    hlo = jax.jit(lambda q, cache, bt, lens: paged_decode_attention_mq(
        q, cache, jnp.int32(0), bt, lens, lens - 1)).lower(
        sds((rows, 1, h, d), jnp.bfloat16),
        sds((1, N_BLOCKS, 2, BS, hk * d), jnp.bfloat16),
        sds((rows, m), jnp.int32), sds((rows,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in hlo


def _expert_geoms():
    from benchmarks.probe_kernels import EXPERT_GEOMS

    return sorted(EXPERT_GEOMS)


@pytest.mark.parametrize("cell", _expert_geoms())
def test_grouped_matmul_compiles_at_every_cell_shape(topo, cell):
    """The experts' grouped matmul alone at the four MoE cells' widths and
    dispatches (``benchmarks/probe_kernels.py experts``), both projections'
    shapes, reading the last layer of a stacked [L·E, K, N] array: the block
    the tiling rule picks fits the scoped VMEM, the compiler takes the
    kernel, and the stack is its operand as it lies — no copy of a layer's
    experts among the temporaries."""
    from benchmarks.probe_kernels import EXPERT_GEOMS
    from dynamo_tpu.ops.pallas import grouped_matmul as gmm
    from dynamo_tpu.ops.pallas import registry

    g = EXPERT_GEOMS[cell]
    layers, e = 2, g["held"]
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    for tokens, _ in g["calls"].values():
        m = tokens * g["k"]
        # gate and up in one call, down alone
        for k, n, stacks in ((g["dm"], g["f"], 2), (g["f"], g["dm"], 1)):
            tm = registry.grouped_matmul_row_tile(m, max(k, n))
            tn = registry.grouped_matmul_tiling(tm, k, n, weights=stacks)
            assert registry.grouped_matmul_vmem_bytes(
                tm, tn, k, weights=stacks) <= registry.SCOPED_VMEM_BYTES

            def fn(xs, w, sizes, layer, m=m, tm=tm, stacks=stacks):
                plan = gmm.grouped_matmul_plan(sizes, m, tm)
                return gmm.grouped_expert_matmul(
                    xs, (w,) * stacks, plan, layer * e, tm=tm)

            compiled = jax.jit(fn).lower(
                sds((m, k), jnp.bfloat16),
                sds((layers * e, k, n), jnp.bfloat16),
                sds((e,), jnp.int32), sds((), jnp.int32)).compile()
            assert "grouped_expert_matmul" in compiled.as_text()
            temp = compiled.memory_analysis().temp_size_in_bytes
            assert temp < k * n * 2, (m, k, n, temp)


@pytest.mark.parametrize("case", ["many-rows", "mesh"])
def test_grouped_matmul_leaves_many_rows_and_meshes_to_ragged_dot(
        topo, tpu_gate, case):
    """``grouped_expert_dispatch`` over Qwen3's stacked experts, on the
    described chip(s): twice the rows an expert the rule takes the kernel
    for, and a decode step's rows under a two-device mesh that shards F —
    both are XLA's ``ragged-dot``, and neither holds the kernel."""
    from dynamo_tpu.models.llama import grouped_expert_dispatch
    from dynamo_tpu.ops.pallas import registry

    layers, e, k_top, d, f = 2, 128, 8, 2048, 768
    cap = registry.GROUPED_MATMUL_MAX_ROWS_PER_GROUP
    if case == "mesh":
        mesh = Mesh(np.array(topo.devices[:2]).reshape(1, 2),
                    ("data", "model"))
        place = lambda spec=P(): NamedSharding(mesh, spec)
        under = lambda: jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
        t = 32
    else:
        place = lambda spec=P(): SingleDeviceSharding(topo.devices[0])
        under, t = contextlib.nullcontext, 2 * cap * e // k_top
    sds = lambda shape, dt, spec=P(): jax.ShapeDtypeStruct(
        shape, dt, sharding=place(spec))
    up, down = P(None, None, None, "model"), P(None, None, "model", None)

    def fn(xf, weights, topi, w_gate, w_up, w_down, layer):
        with under():
            return grouped_expert_dispatch(
                xf, weights, topi, e, w_gate, w_up, w_down, jax.nn.silu,
                layer=layer)

    hlo = jax.jit(fn).lower(
        sds((t, d), jnp.bfloat16), sds((t, k_top), jnp.float32),
        sds((t, k_top), jnp.int32), sds((layers, e, d, f), jnp.bfloat16, up),
        sds((layers, e, d, f), jnp.bfloat16, up),
        sds((layers, e, f, d), jnp.bfloat16, down),
        sds((), jnp.int32)).compile().as_text()
    assert hlo.count("ragged-dot-none") >= 3
    assert "grouped_expert_matmul" not in hlo


def _kernel_body_converts(fn, *args):
    """(operand avals of the decode kernel's pallas_call, [(from dtype,
    shape)] of every conversion to float32 inside its body, nested
    branches and loops included)."""
    def walk(jaxpr, found):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "convert_element_type"
                    and eqn.params["new_dtype"] == jnp.float32):
                aval = eqn.invars[0].aval
                found.append((str(aval.dtype), tuple(aval.shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, found)
        return found

    def calls(jaxpr, out):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "pallas_call"
                    and "paged_decode_attention" in eqn.params["name"]):
                out.append(eqn)
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    calls(sub, out)
        return out

    (call,) = calls(jax.make_jaxpr(fn)(*args).jaxpr, [])
    return ([v.aval for v in call.invars], walk(call.params["jaxpr"], []))


@pytest.mark.parametrize("case", ["mistral-7b-decode", "mistral-7b-tp4-decode"])
def test_decode_program_holds_no_float32_query_or_cache_block(
        topo, tpu_gate, case):
    """PR 47: the block-diagonal query goes to the kernel in the model's
    dtype and K/V go to the matrix unit as the cache holds them.  Compiled
    for the described v5e (one chip; four under ``--tp 4``, a shard's 8
    query rows and 256 lanes) the decode program holds no float32
    [B, S*H, Hk*D] array, and traced, the kernel's body converts no K/V
    chunk to float32."""
    hlo, _ = _scan_program(topo, case)
    config_file, _, chips, overrides = _SCAN_PROGRAMS[case]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "cellbench/configs", config_file)) as f:
        hf = json.load(f)
    b = hf["serve"]["max_batch_size"]
    h = hf["num_attention_heads"] // chips
    hkd = hf["num_key_value_heads"] * hf["head_dim"] // chips
    assert "tpu_custom_call" in hlo
    assert f"bf16[{b},{h},{hkd}]" in hlo           # the kernel's q and output
    assert f"f32[{b},{h},{hkd}]" not in hlo

    # the kernel's body, traced at that geometry (a shard's under --tp 4)
    sds = jax.ShapeDtypeStruct
    fn = functools.partial(pa.paged_attention_layer, sm_scale=128 ** -0.5)
    if chips > 1:
        mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                    ("data", "model"))
        place = lambda spec: NamedSharding(mesh, spec)
        inner = fn
        def fn(*a):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return inner(*a)
    else:
        place = lambda spec: SingleDeviceSharding(topo.devices[0])
    arg = lambda shape, dt, spec=P(): sds(shape, dt, sharding=place(spec))
    operands, converts = _kernel_body_converts(
        fn, arg((b, 1, h * chips, 128), jnp.bfloat16, _HEADS),
        arg((1, N_BLOCKS, 2, BS, hkd * chips), jnp.bfloat16, _CACHE_SPEC),
        arg((), jnp.int32), arg((b, M), jnp.int32), arg((b,), jnp.int32),
        arg((b, 1), jnp.int32))
    assert any(a.shape == (b, h, hkd) and a.dtype == jnp.bfloat16
               for a in operands), operands
    assert not any(a.dtype == jnp.float32 and a.ndim >= 3 for a in operands)
    wide = [cv for cv in converts if cv[1][-1:] == (hkd,) and cv[0] != "float32"]
    assert not wide, wide


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill-one-block"])
@pytest.mark.parametrize("config", ["ouro-2.6b", "mistral-7b"])
def test_one_chip_programs_keep_one_cache(topo, tpu_gate, config, program):
    """ROADMAP S7: "the one-chip decode program reserves a second copy of
    the whole cache" was the *prefill* program of a chunk of exactly one
    block — every short prompt — whose one-update scatter XLA lowered to a
    select over the whole cache.  No program of either configuration holds
    more temporaries than a fraction of its cache, at the cache the file
    asks for."""
    hf, cfg, model, params, cache, sds = _abstract_model(
        config + ".json", lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    if program == "prefill-one-block":
        program, serve["prefill_chunk_tokens"] = "prefill", serve["block_size"]
    fn, args = _step_program(program, model, serve, sds)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention") in hlo
    # the layer scan, and round it the loop of passes where there is one
    assert hlo.count(" while(") == (2 if cfg.ut_steps > 1 else 1)
    mem = compiled.memory_analysis()
    cache_bytes = cache.size * cache.dtype.itemsize
    assert mem.alias_size_in_bytes >= cache_bytes           # donated
    assert mem.temp_size_in_bytes < cache_bytes // 8, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM, total
    if config == "ouro-2.6b":       # whole, and the chip full
        assert hf["reduced"] == [] and model.cache_layers == 192
        assert cache_bytes >= 9 * 2**30 and total > 14 * 2**30


def test_tp_rules_that_keep_the_xla_path(tpu_gate):
    """The static, named exceptions under a mesh — made before tracing."""
    kw = dict(num_kv_heads=8, block_size=BS)
    assert pa.attention_impl("decode", tp=4, quant=True, **kw)[0] == "xla"
    assert pa.attention_impl("decode", tp=16, **kw)[0] == "xla"
    # a sliding window is no exception since PR 60 (the kernels' windowed
    # form), and leaves the mesh's and int8's rules as they are
    assert pa.attention_impl("prefill", windowed=True, **kw) == (
        "pallas", "tpu, windowed kernel")
    assert pa.attention_impl("decode", windowed=True, tp=4, **kw) == (
        "pallas", "tpu, shard_map over tp=4, windowed kernel")
    assert pa.attention_impl("decode", windowed=True, tp=4, quant=True,
                             **kw)[0] == "xla"
    assert pa.attention_impl(
        "decode", num_kv_heads=8, block_size=16, quant=True)[0] == "xla"


# ---------------------------------------------------------------------------
# GLM-5.2 (cellbench/configs/glm-5.2-ep16.json): the kernels of a latent
# cache held once, at the published widths (64 heads, rows of 576 elements =
# 384 words, index_topk 2,048) and the cell's pool (14,400 blocks of 32).
# What interpret mode could not say, and the chip's compiler did: a one-row
# slice of an (8, 128)-tiled array is refused (hence the unit axis), a row
# list shorter than the 1,024-word tiling of a flat int32 array is refused
# (hence the padding), and XLA's own scatter re-lays the whole cache out
# (hence the DMA movers: the whole-cache copy shows as temp bytes).
GLM = dict(h=64, width=576, words=384, topk=2048, layers=5, blocks=14400)


@pytest.fixture
def glm_sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("phase,n,k", [
    ("decode", 32, 2048), ("prefill", 256, 2048), ("prefill", 32, 32)])
def test_sparse_latent_attention_compiles_on_one_chip(
        glm_sds, tpu_gate, phase, n, k):
    g = GLM
    latent = glm_sds((g["layers"], g["blocks"], BS, 1, g["words"]), jnp.uint32)
    fn = functools.partial(pa.sparse_latent_attention, sm_scale=1 / 16,
                           phase=phase)
    compiled = jax.jit(fn).lower(
        glm_sds((n, g["h"], g["width"]), jnp.bfloat16), latent,
        glm_sds((), jnp.int32), glm_sds((n, k), jnp.int32),
        glm_sds((n,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"mla_sparse_{phase}" in text
    # the cache is read where it lies: no copy of it among the temporaries
    cache_bytes = g["layers"] * g["blocks"] * BS * g["words"] * 4
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 10


# temporaries of the 2,048-token chunk at 34,816 tokens of context, compiled
# for this device as the question is below (1,284,287,488; the
# configuration's ``serve_why`` counts on 1.28 GB) - 70 CPU-seconds to
# compile, so held here as a number
GLM_CHUNK_TEMP = 1.28e9
# one ``full`` layer and one that shares its selection: what the five do
_GLM_KINDS = {"num_hidden_layers": 2, "first_k_dense_replace": 1,
              "indexer_types": ["full", "shared"],
              "mlp_layer_types": ["dense", "sparse"]}
_FROM_A_GATHER = re.compile(r'op_name="[^"]*/indexer/[^"]*gather"')


@pytest.mark.parametrize("chunk", [None, 256], ids=["decode", "question-256"])
def test_indexer_lists_its_selection_without_an_element_gather(
        topo, tpu_gate, chunk, monkeypatch):
    """From the selection mask to the slot list attention reads, the indexer
    gathers nothing by the element (three ``take_along_axis`` of 65,536 -
    524,288 scalars a ``full`` layer were 12% of a decode program and a
    quarter of a question program: PERF.md, PR 44).  A question gathers its
    context's keys, whole blocks of ``index_k``, and holds no more
    temporaries than a 2,048-token chunk, the program that sizes the cell's
    memory (0.11 GB: 0.15 with the gathers).  A decode step gathers nothing
    under the scope at all: ``dsa_index_scores`` reads the keys where they
    lie, once a ``full`` layer, and the rows that ask one document are
    grouped once a step, outside the layer scans, for both (PR 67: the copy
    of the whole table's keys, ``bf16[32, 36864, 128]``, was 302 MB a layer
    every step)."""
    from dynamo_tpu.ops import latent_cache

    grouped = []
    real = latent_cache.index_decode_groups
    monkeypatch.setattr(
        latent_cache, "index_decode_groups",
        lambda *a, **kw: grouped.append(1) or real(*a, **kw))
    # a decode step: the two ``full`` layers of the cell's five; a question:
    # one ``full`` layer and one that shares its selection
    kinds = _GLM_KINDS if chunk else dict(
        _GLM_KINDS, indexer_types=["full", "full"])
    hf, cfg, model, params, cache, sds = _abstract_model(
        "glm-5.2-ep16.json",
        lambda spec: SingleDeviceSharding(topo.devices[0]), **kinds)
    # the cell's decode program, or a question behind the longest prefix
    fn, args = _step_program(
        "prefill" if chunk else "decode", model,
        dict(hf["serve"], prefill_chunk_tokens=chunk), sds,
        prefix_blocks=1024)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    # a question after a prefix hit attends masked, a decode step gathers
    assert ("mla_sparse_prefill_masked" if chunk else "mla_sparse_decode") \
        in hlo
    assert ("mla_sparse_prefill" in hlo) == bool(chunk)
    gathered = set()
    for line in hlo.splitlines():
        m = _HLO_INSTR.match(line)
        # XLA lowers a gather to a custom fusion that keeps its name
        if (m and m.group(4) in ("gather", "fusion")
                and _FROM_A_GATHER.search(line)):
            dims = tuple(int(d) for d in m.group(3).split(","))
            gathered.add((m.group(2), dims[-2:]))
    scored = [line for line in hlo.splitlines()
              if "custom-call(" in line and "dsa_index_scores" in line]
    if chunk:
        assert gathered == {("bf16", (BS, 128))}, gathered  # [.., Bs, Di]
        assert not scored and not grouped
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= GLM_CHUNK_TEMP, temp
        return
    assert not gathered, gathered
    # the kernel, once a ``full`` layer, under the scope the benchmark reads
    # the indexer's time by; the groups once for both
    assert len(scored) == 2 and all("/indexer/" in line for line in scored)
    assert len(grouped) == 1
    # no copy of the table's keys: no array of keys a position of the table
    # is written (the cache itself is [2, 14400, 32, 128], in place)
    copies = [(name, dims, op) for name, dtype, dims, op
              in _unfused_instructions(hlo)
              if dtype == "bf16" and dims[-1:] == (128,)
              and math.prod(dims) >= 32 * 36864 * 128
              and dims != tuple(cache["index_k"].shape)
              and op not in _PASS_THROUGH]
    assert not copies, copies
    index_bytes = cache["index_k"].size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < index_bytes


@pytest.mark.parametrize("rows", [32, 5], ids=["the-cell", "five-rows"])
def test_index_scores_kernel_compiles_on_one_chip(glm_sds, rows):
    """``dsa_index_scores`` at the cell's shapes — 32 rows of 32 heads of
    128 over a table of 1,152 blocks of the two ``full`` layers' keys, every
    row's scores [32, 36864] resident in VMEM — and at a batch that is no
    power of two (the groups' cap follows it).  The call carries the kernel's
    own name, which matches neither pattern the cell's attention rooflines
    read (``^mla_sparse_decode``, ``^mla_sparse_prefill``); the keys are read
    where they lie: no temporary but the output's own."""
    from dynamo_tpu.ops.pallas import dsa_index_scores as dsa

    assert dsa.fits(rows, 1152, BS, 32, 128)
    compiled = jax.jit(dsa.dsa_index_scores).lower(
        glm_sds((rows, 32, 128), jnp.bfloat16),
        glm_sds((rows, 32), jnp.bfloat16),
        glm_sds((2 * GLM["blocks"], BS, 128), jnp.bfloat16),
        glm_sds((rows, 1152), jnp.int32), glm_sds((rows,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and dsa.KERNEL_NAME in text
    assert not re.match(r"mla_sparse_(decode|prefill)", dsa.KERNEL_NAME)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("s,c", [(2048, 34816), (256, 33280), (64, 16896)],
                         ids=["chunk", "question-256", "question-64"])
def test_masked_latent_prefill_compiles_on_one_chip(glm_sds, s, c):
    """The masked form at a document chunk's and at two questions' shapes,
    the context padded to whole 512-key tiles as ``masked_attention`` pads
    it; the call carries the operation's name, which cellbench's
    ``kernel.prefill_attn_roofline`` reads (``^mla_sparse_prefill``)."""
    from dynamo_tpu.ops.pallas.mla_masked_prefill import (
        mla_sparse_prefill_masked,
    )

    h = GLM["h"]
    compiled = jax.jit(functools.partial(
        mla_sparse_prefill_masked, heads=h, dv=512, sm_scale=1 / 16)).lower(
            glm_sds((s * h, 640), jnp.bfloat16),
            glm_sds((c, 640), jnp.bfloat16),
            glm_sds((s, c), jnp.float32), glm_sds((2,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_sparse_prefill_masked" in text


def test_latent_cache_movers_compile_and_copy_no_cache(glm_sds, tpu_gate):
    from dynamo_tpu.ops import latent_cache

    g = GLM
    latent = glm_sds((g["layers"], g["blocks"], BS, 1, g["words"]), jnp.uint32)
    cache_bytes = g["layers"] * g["blocks"] * BS * g["words"] * 4
    write = jax.jit(latent_cache.write_latent, donate_argnums=(0,)).lower(
        latent, glm_sds((), jnp.int32), glm_sds((2048, g["words"]), jnp.uint32),
        glm_sds((2048,), jnp.int32)).compile()
    assert "latent_cache_write_rows" in write.as_text()
    stats = write.memory_analysis()
    assert stats.alias_size_in_bytes >= cache_bytes       # written in place
    assert stats.temp_size_in_bytes < cache_bytes / 10
    read = jax.jit(latent_cache.context_rows).lower(
        latent, glm_sds((), jnp.int32), glm_sds((1, 1088), jnp.int32)).compile()
    assert "latent_cache_gather_blocks" in read.as_text()
    assert read.memory_analysis().temp_size_in_bytes < cache_bytes / 10


# ---------------------------------------------------------------------------
# Mistral-Small-4 (cellbench/configs/mistral-small-4-ep8.json): the dense
# kernels over the latent cache held once, at the published widths (32 heads,
# rows of 320 elements in 384 lanes) and the cell's pool (14,400 blocks of 32
# in 9 layers), and the cell's whole programs at its own ``serve`` geometry.
M4 = dict(h=32, wd=384, dv=256, layers=9, blocks=14400)


def test_dense_latent_decode_compiles_on_one_chip(glm_sds):
    from dynamo_tpu.ops.pallas.mla_dense_attention import mla_dense_decode

    g = M4
    compiled = jax.jit(functools.partial(mla_dense_decode, dv=g["dv"])).lower(
        glm_sds((32, g["h"], g["wd"]), jnp.bfloat16),
        glm_sds((g["layers"] * g["blocks"], BS, g["wd"]), jnp.bfloat16),
        glm_sds((32, 1152), jnp.int32), glm_sds((32,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_dense_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("s,blocks", [(2048, 1088), (256, 1032), (32, 1)],
                         ids=["chunk", "question", "one-block"])
def test_dense_latent_prefill_compiles_on_one_chip(glm_sds, s, blocks):
    from dynamo_tpu.ops.pallas.mla_dense_attention import mla_dense_prefill

    g = M4
    compiled = jax.jit(functools.partial(
        mla_dense_prefill, heads=g["h"], dv=g["dv"])).lower(
            glm_sds((s * g["h"], g["wd"]), jnp.bfloat16),
            glm_sds((g["layers"] * g["blocks"], BS, g["wd"]), jnp.bfloat16),
            glm_sds((blocks,), jnp.int32), glm_sds((2,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "mla_dense_prefill" in text and "latent_cache_gather_blocks" in text


@pytest.mark.parametrize("program,chunk,prefix", [
    ("decode", None, 1), ("prefill", 256, 1024), ("prefill", 2048, 1024)],
    ids=["decode", "question-256", "chunk-2048"])
def test_mistral4_cell_programs_keep_one_cache_and_name_their_kernels(
        topo, tpu_gate, program, chunk, prefix, monkeypatch):
    """The cell's decode program, a question behind the longest document and
    a document's last chunk, whole (9 layers, 16 experts, the cell's cache):
    the dense kernel by name, one layer scan, the cache donated and written
    in place by XLA's own scatter (no second copy, no re-layout), and
    weights + cache + temporaries inside the chip (11.7-11.8 GB).  A stack
    without an indexer asks for none of the indexer's groups: its programs
    are their parent's (PR 67)."""
    from dynamo_tpu.ops import latent_cache

    def never(*a, **kw):
        raise AssertionError("a stack with no indexer asked for its groups")

    monkeypatch.setattr(latent_cache, "index_decode_groups", never)
    hf, cfg, model, params, cache, sds = _abstract_model(
        "mistral-small-4-ep8.json",
        lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    if chunk:
        serve["prefill_chunk_tokens"] = chunk
    fn, args = _step_program(program, model, serve, sds, prefix_blocks=prefix)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert f"mla_dense_{program}" in hlo and hlo.count(" while(") == 1
    if program == "decode":
        # one call a layer — a row alone and a full group are the same
        # kernel — in the scan's body; the rows' groups are found outside it,
        # once a step; and no row's context is gathered: what is written in
        # rows of 384 lanes is the cache in place, the step's 32 new rows and
        # the queries [32, 32, 384]
        calls, comp = [], ""
        for line in hlo.splitlines():
            head = _HLO_COMPUTATION.match(line)
            comp = head.group(1) if head else comp
            if "custom-call(" in line and "mla_dense_decode" in line:
                calls.append(comp)
        assert len(calls) == 1 and "region" in calls[0], calls
        compares = [line for line in hlo.splitlines()
                    if re.search(r"pred\[32,1152\]\S* (compare|fusion)\(", line)]
        assert compares, "the tables' comparison: decode_groups"
        in_body = hlo[hlo.index(f"%{calls[0]} ("):].split("\n}\n")[0]
        assert "[32,1152]" not in in_body.replace("s32[32,1152]", "")
        rows = {(op, dims) for _, dtype, dims, op in _unfused_instructions(hlo)
                if dtype == "bf16" and dims[-1:] == (384,)
                and op not in _PASS_THROUGH}
        assert rows and all(
            math.prod(dims) in (9 * 14400 * BS * 384, 32 * 32 * 384, 32 * 384)
            for _, dims in rows), rows
    # 1 row an expert (decode), 8 (a question) and 64 (a document's chunk),
    # all inside a row tile: the grouped matmul's kernel (gate + up, down)
    # and no ragged-dot custom call
    assert len(_grouped_matmul_calls(hlo)) == 2
    assert "ragged-dot" not in hlo
    mem = compiled.memory_analysis()
    cache_bytes = cache["latent"].size * 2
    assert cache_bytes == 14400 * 32 * 9 * 768
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 16, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0.6 * V5E_HBM < total < 0.75 * V5E_HBM, total


@pytest.fixture(scope="module", params=["decode", "prefill"])
def solar_program(request, topo):
    """(program, model, cache, compiled): solar-open2-ep16's decode program
    or its 512-token chunk, whole, compiled for the described v5e once a
    module.  The chunk alone is ~56 CPU-seconds of XLA on several threads:
    paid here, in the set-up, it is not charged to the test that reads it
    (tests/conftest.py budgets a test's call)."""
    program = request.param
    with pytest.MonkeyPatch.context() as patch:     # as ``tpu_gate``
        patch.setattr(jax, "default_backend", lambda: "tpu")
        hf, cfg, model, params, cache, sds = _abstract_model(
            "solar-open2-ep16.json",
            lambda spec: SingleDeviceSharding(topo.devices[0]))
        assert hf["attention_layers"] == len(cfg.gqa_layers) == 2
        fn, args = _step_program(program, model, dict(hf["serve"]), sds,
                                 prefix_blocks=16)
        if program == "prefill":        # the engine names the row's slot
            from dynamo_tpu.engine.core import unified_step

            fn = lambda p, c, *a: unified_step(
                model, p, c, *a[:-1], prefix_blocks=16, seq_slots=a[-1])
            args = (*args, sds((1,)))
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *args).compile()
    return program, model, cache, compiled


def test_hybrid_linear_cell_programs_write_the_state_in_place(
        solar_program, tpu_gate):
    """solar-open2-ep16's decode program (64 rows = the slot array) and a
    512-token chunk with 16 blocks of the prompt cached, whole (8 layers, 20
    experts, the cell's cache and its 64 slots of state): the GQA layers
    through the Pallas kernels by name, one scan a run of layers of a kind
    (XLA unrolls a chunk's eight 64-token pieces inside the linear runs),
    every leaf of the cache donated and written in place — no copy of
    the 1.6 GB state among the temporaries — and weights + state + cache
    inside the chip (~11 GB).  The decode program updates the state by the
    kernel of ops/pallas/linear_state.py (compiled here for the v5e with no
    VMEM limit of its own: inside the compiler's scoped 16 MiB)."""
    program, model, cache, compiled = solar_program
    hlo = compiled.as_text()
    assert ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention") in hlo
    # 1.6 rows an expert (decode) and 6.4 (a chunk): the grouped matmul's
    # kernel, two calls (gate + up, down) a run of layers, and no ragged-dot
    assert len(_grouped_matmul_calls(hlo)) == 2 * 4
    assert "ragged-dot" not in hlo
    # G | L L L | G | L L L
    assert hlo.count(" while(") == 4
    # the state is sliced and updated where it lies, never copied whole
    assert not re.search(r"f32\[6,64,64,128,128\]\S* copy\(", hlo)
    if program == "decode":
        # ... and by one kernel a linear layer, inside the scope that
        # kernel.linear_attn_roofline reads (readers/scope_roofline.py):
        # no XLA pass over a layer's 268 MB is left under it
        calls = [line for line in hlo.splitlines()
                 if "custom-call(" in line and "linear_state_update" in line]
        assert len(calls) == 2                     # one a run of L L L
        for line in calls:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "linear_state" in op_name.split("/"), op_name
        assert "f32[64,64,128,128]" not in hlo
        # the convolution's tails are written before the output projection
        # (an optimization_barrier in _linear): left to float behind the
        # experts, the whole 57 MB leaf rode the layer scan in VMEM and was
        # copied out and back under every layer's first projection
        assert not re.search(
            r"bf16\[6,64,3,24576\]\{3,2,1,0:[^}]*S\(1\)\}", hlo)
        assert model.state_update_impl() == ("pallas", "tpu")
        from dynamo_tpu.ops.pallas import registry

        # 16 heads' matrices a grid step, double buffered in and out: 4 MiB
        # of the 16 the compiler gives a kernel that asks for no more
        group = registry.linear_state_heads_per_step(64)
        assert group == registry.LINEAR_STATE_HEADS_PER_STEP == 16
        assert (2 * registry.DOUBLE_BUFFER * group * 128 * 128 * 4
                == registry.SCOPED_VMEM_BYTES // 4)
    mem = compiled.memory_analysis()
    nbytes = lambda a: a.size * a.dtype.itemsize
    state, pool = nbytes(cache["state"]), nbytes(cache["kv"])
    assert state == 6 * 64 * 64 * 128 * 128 * 4 and pool == 6272 * 32 * 8192
    held = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= held                   # all donated
    assert mem.temp_size_in_bytes < state // 4, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"total {total / 1e9:.3f} GB")
    assert 0.6 * V5E_HBM < total < 0.75 * V5E_HBM, total


@pytest.fixture(scope="module", params=[
    "decode", "prefill",
    # ~350 CPU-seconds of XLA (128 experts a layer under a scan, the
    # token-by-token recurrence): outside tier-1
    pytest.param("reference", marks=pytest.mark.slow)])
def ling_program(request, topo):
    """(program, hf, cfg, model, params, cache, compiled):
    ling-3.0-flash-ep4's decode program, its 512-token chunk or the check's
    float32 reference over 768 tokens, whole, compiled for the described v5e
    once a module.  The chunk alone is ~54 CPU-seconds of XLA: paid here, in
    the set-up, it is not charged to the test that reads it
    (tests/conftest.py budgets a test's call)."""
    program = request.param
    with pytest.MonkeyPatch.context() as patch:     # as ``tpu_gate``
        patch.setattr(jax, "default_backend", lambda: "tpu")
        hf, cfg, model, params, cache, sds = _abstract_model(
            "ling-3.0-flash-ep4.json",
            lambda spec: SingleDeviceSharding(topo.devices[0]))
        if program == "reference":
            from cellbench import spec

            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            ref = spec.load_module(root, "reference", hf["reference"])
            compiled = jax.jit(ref.make_forward(hf)).lower(
                params, sds((768,)), sds((8,))).compile()
        else:
            fn, args = _step_program(program, model, dict(hf["serve"]), sds,
                                     prefix_blocks=16)
            if program == "prefill":    # the engine names the row's slot
                from dynamo_tpu.engine.core import unified_step

                fn = lambda p, c, *a: unified_step(
                    model, p, c, *a[:-1], prefix_blocks=16, seq_slots=a[-1])
                args = (*args, sds((1,)))
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, *args).compile()
    return program, hf, cfg, model, params, cache, compiled


def test_ling_cell_programs_hold_a_latent_cache_beside_the_state(
        ling_program, tpu_gate):
    """ling-3.0-flash-ep4's decode program (64 rows = the slot array), a
    512-token chunk with 16 blocks of the prompt cached, and the check's
    float32 reference over its longest sequence (700 + 8 tokens, padded to
    768), whole (layer 0 K+dense | K x5 | M, 128 experts of 512, the cell's
    latent pool and its 64 slots of state): the MLA layer through the dense
    latent kernels by name at the new geometry (rows of 640 lanes, 32 heads,
    value 512), the decode's state update by the delta rule's kernel inside
    the scopes the cell's metrics read, the experts through the grouped
    matmul at 1 row an expert (K 2,560 / N 768), one scan a run of layers,
    every leaf of the cache donated and written in place, and weights +
    state + latent rows inside the chip (~11.6 GB) with room for the
    reference's temporaries beside them."""
    program, hf, cfg, model, params, cache, compiled = ling_program
    nbytes = lambda a: a.size * a.dtype.itemsize
    weights = sum(nbytes(a) for a in jax.tree.leaves(params))
    held = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert 10.45e9 < weights < 10.47e9
    state, pool = nbytes(cache["state"]), nbytes(cache["latent"])
    assert state == 6 * 64 * 32 * 128 * 128 * 4 and pool == 6272 * 32 * 1280
    assert hf["attention_layers"] == len(cfg.gqa_layers) == 1
    mem = compiled.memory_analysis()
    if program == "reference":
        print(f"# reference: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
        # beside the served model: its weights are the reference's
        # arguments, the cache is the engine's; a layer's 128 experts are
        # sliced and upcast one at a time (whole they are 2.8 GB)
        assert mem.temp_size_in_bytes < 1.6e9, mem.temp_size_in_bytes
        assert (weights + held + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < 0.93 * V5E_HBM
        return
    hlo = compiled.as_text()
    assert f"mla_dense_{program}" in hlo
    # the expert layers' grouped matmul, two calls (gate + up, down) a run of
    # expert layers (K x5 | M), and no ragged-dot
    assert len(_grouped_matmul_calls(hlo)) == 2 * 2
    assert "ragged-dot" not in hlo
    # K+dense | K K K K K | M
    assert hlo.count(" while(") == 3
    assert not re.search(r"f32\[6,64,32,128,128\]\S* copy\(", hlo)
    for line in hlo.splitlines():
        if "custom-call(" in line and f"mla_dense_{program}" in line:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "latent_attn" in op_name.split("/"), op_name
    if program == "decode":
        calls = [line for line in hlo.splitlines()
                 if "custom-call(" in line and "linear_state_update" in line]
        assert len(calls) == 2          # the dense layer's run, and K x5
        for line in calls:
            scopes = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
            assert "delta_rule" in scopes and "linear_state" in scopes, scopes
        assert "f32[64,32,128,128]" not in hlo
        assert model.state_update_impl() == ("pallas", "tpu")
    assert mem.alias_size_in_bytes >= held                   # all donated
    assert mem.temp_size_in_bytes < state // 4, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"total {total / 1e9:.3f} GB")
    assert 0.6 * V5E_HBM < total < 0.75 * V5E_HBM, total


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_hybrid_cell_programs_write_the_state_in_place(
        topo, tpu_gate, program):
    """granite-4.0-h-small-ep2's decode program (64 rows = the slot array)
    and a 512-token chunk (two SSD pieces of 256) with 16 blocks of the
    prompt cached, whole (10 layers m m m m m A m m m m, 36 experts, the
    cell's cache and its 64 slots of state): the one attending layer through
    the Pallas GQA kernels at 32/8 heads, the experts through the grouped
    matmul at K 4096 / N 768 (8.9 rows an expert in decode, 71 in a chunk),
    one scan a run of layers, every leaf of the cache donated and written in
    place, and weights + state + K/V inside the chip (~12.8 GB).  The decode
    program updates the state by the kernel of ops/pallas/ssm_state.py; a
    chunk's recurrence stays XLA's, with no custom call of its own."""
    hf, cfg, model, params, cache, sds = _abstract_model(
        "granite-4.0-h-small-ep2.json",
        lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    assert hf["attention_layers"] == len(cfg.gqa_layers) == 1
    assert model.state_update_impl() == ("pallas", "tpu")
    fn, args = _step_program(program, model, serve, sds, prefix_blocks=16)
    if program == "prefill":        # the engine names the row's slot
        from dynamo_tpu.engine.core import unified_step

        fn = lambda p, c, *a: unified_step(
            model, p, c, *a[:-1], prefix_blocks=16, seq_slots=a[-1])
        args = (*args, sds((1,)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention") in hlo
    assert len(_grouped_matmul_calls(hlo)) == 2 * 3     # m x5 | A | m x4
    assert "ragged-dot" not in hlo
    assert "linear_state_update" not in hlo
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "ssm_state_update" in line]
    # m x5 | m x4: the run of one attending layer is a scan of one step,
    # which XLA inlines (a chunk's two pieces are a scan of their own)
    assert hlo.count(" while(") == (2 if program == "decode" else 4)
    # the state is sliced and updated where it lies, never copied whole
    assert not re.search(r"f32\[9,64,128,64,128\]\S* copy\(", hlo)
    if program == "decode":
        # ... and by one kernel a Mamba layer, inside the scope that
        # kernel.ssm_state_roofline reads (readers/scope_roofline.py): no
        # XLA pass over a layer's 268 MB is left in the program
        assert len(calls) == 2                     # m x5 | m x4
        for line in calls:
            op_name = re.search(r'op_name="([^"]*)"', line).group(1)
            assert "ssm_state" in op_name.split("/"), op_name
        assert "f32[64,128,64,128]" not in hlo
        # the convolution's tails are not carried through the layer scan
        # in VMEM (what Solar's program did once its state's slice and set
        # were gone: test_hybrid_linear_cell_programs_...)
        assert not re.search(
            r"bf16\[9,64,3,8448\]\{[\d,]*:[^}]*S\(1\)\}", hlo)
        from dynamo_tpu.ops.pallas import registry

        # 32 heads' matrices a grid step, double buffered in and out: 4 MiB
        # of the 16 the compiler gives a kernel that asks for no more
        group = registry.ssm_state_heads_per_step(128, 1, 64, 128)
        assert group * 64 * 128 * 4 == registry.SSM_STATE_BLOCK_BYTES
        assert (2 * registry.DOUBLE_BUFFER * registry.SSM_STATE_BLOCK_BYTES
                == registry.SCOPED_VMEM_BYTES // 4)
    else:
        assert not calls
    mem = compiled.memory_analysis()
    nbytes = lambda a: a.size * a.dtype.itemsize
    state, pool = nbytes(cache["state"]), nbytes(cache["kv"])
    assert state == 9 * 64 * 128 * 64 * 128 * 4 and pool == 6272 * 32 * 4096
    assert cache["conv"].shape == (9, 64, 3, 8448)
    held = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= held                   # all donated
    assert mem.temp_size_in_bytes < state // 4, mem.temp_size_in_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"total {total / 1e9:.3f} GB")
    assert 12.7e9 < mem.argument_size_in_bytes < 12.9e9
    assert 0.74 * V5E_HBM < total < 0.82 * V5E_HBM, total


@pytest.mark.parametrize("program", ["decode", "prefill", "reference"])
def test_zaya_cell_programs_fit_the_chip_with_every_leaf_in_place(
        topo, tpu_gate, program):
    """zaya1-8b's decode program (64 rows = the slot array), a 512-token
    chunk with 16 blocks of the prompt cached, and the check's float32
    reference over its longest sequence (700 + 8 tokens, padded to 768),
    whole (20 layers, 16 experts, the 262,272-row tied matrix, the cell's
    pool and its 64 slots of tails): every layer through the Pallas GQA
    kernels at 8/2 heads of 128 (256 lanes a K/V row), the experts through
    the grouped matmul at K 2,048 / N 2,048 (4 rows an expert in decode, 32
    in a chunk), one scan over the layers, every leaf of the cache donated
    and written in place, and weights + K/V + tails inside the chip
    (~13.5 GB) with room for the reference's temporaries beside them."""
    hf, cfg, model, params, cache, sds = _abstract_model(
        "zaya1-8b.json", lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    nbytes = lambda a: a.size * a.dtype.itemsize
    weights = sum(nbytes(a) for a in jax.tree.leaves(params))
    held = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert 9.37e9 < weights < 9.39e9
    assert nbytes(cache["kv"]) == 6272 * 32 * 20 * 1024      # 4.11 GB
    assert cache["state"].shape == (20, 64, 2688)
    if program == "reference":
        from cellbench import spec

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ref = spec.load_module(root, "reference", hf["reference"])
        compiled = jax.jit(ref.make_forward(hf)).lower(
            params, sds((768,)), sds((8,))).compile()
        mem = compiled.memory_analysis()
        print(f"# reference: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
        # beside the served model: its weights are the reference's
        # arguments, the cache is the engine's
        assert mem.temp_size_in_bytes < 1.6e9, mem.temp_size_in_bytes
        assert (weights + held + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < 0.93 * V5E_HBM
        return
    fn, args = _step_program(program, model, serve, sds, prefix_blocks=16)
    if program == "prefill":        # the engine names the row's slot
        from dynamo_tpu.engine.core import unified_step

        fn = lambda p, c, *a: unified_step(
            model, p, c, *a[:-1], prefix_blocks=16, seq_slots=a[-1])
        args = (*args, sds((1,)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention") in hlo
    assert len(_grouped_matmul_calls(hlo)) == 2      # gate + up, down: one scan
    assert "ragged-dot" not in hlo
    assert hlo.count(" while(") == 1
    # neither the pool nor the tails are copied whole
    assert not re.search(r"bf16\[20,6272,2,32,256\]\S* copy\(", hlo)
    assert not re.search(r"bf16\[20,64,2688\]\S* copy\(", hlo)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held                   # all donated
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"total {total / 1e9:.3f} GB")
    assert 13.4e9 < mem.argument_size_in_bytes < 13.6e9
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    assert 0.79 * V5E_HBM < total < 0.84 * V5E_HBM, total


# ---------------------------------------------------------------------------
# PR 57: a prefill program a token bucket, not a bucket x cached-prefix
# bucket.  On the flash path nothing the program computes reads the static
# ``prefix_blocks`` (the kernel streams the prefix by the true length its
# operands carry), so the engine hands jit one value for every prefix there
# (``EngineCore._prefix_blocks`` through ``ops/paged_attention.py::
# prefill_program_key``) and keeps the bucket wherever it sizes a gather.
_KEY_S, _KEY_PREFIXES = 64, (0, 16, 64)     # a 64-token chunk; cached blocks


def _keyed_toy(family: str, **overrides):
    """A toy of each model class at kernel-friendly widths (heads of 128,
    bfloat16, blocks of 32)."""
    import hybrid_linear_tiny
    import zaya_tiny
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel

    if family == "llama":
        return LlamaModel(ModelConfig.tiny(
            num_kv_heads=2, head_dim=128, dtype="bfloat16", **overrides))
    if family == "hybrid_linear":
        from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                                     HybridLinearModel)
        return HybridLinearModel(HybridLinearConfig.from_hf_config(
            dict(hybrid_linear_tiny.TINY, head_dim=128), dtype="bfloat16"))
    if family == "zaya":
        from dynamo_tpu.models.zaya import ZayaConfig, ZayaModel
        return ZayaModel(ZayaConfig.from_hf_config(
            dict(zaya_tiny.TINY, head_dim=128), dtype="bfloat16"))
    from test_glm_dsa import TINY
    from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
    return GlmDsaModel(GlmDsaConfig.from_hf_config(TINY, dtype="bfloat16"))


def _keyed_engine(model):
    """(engine, lower): an engine round ``model`` on shapes alone, and the
    text of its one-request prefill program lowered for the TPU with a given
    static ``prefix_blocks``, through the engine's own jitted entry point."""
    from dynamo_tpu.engine import EngineConfig, EngineCore, operands
    from dynamo_tpu.engine.sampling import K_MAX

    s = _KEY_S
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=_KEY_PREFIXES[-1] * BS + s,
        block_size=BS, num_blocks=80, prefill_buckets=[s],
        prefill_chunk_tokens=s), eos_token_ids=[])
    i32 = lambda *shape: np.zeros(shape, np.int32)
    f32 = lambda *shape: np.zeros(shape, np.float32)
    bufs, layout = operands.pack((
        i32(),
        (i32(1, s), i32(1, s), i32(1, core.config.max_blocks_per_seq), i32(1),
         i32(1, s), i32(1), None, f32(1), i32(1), f32(1)),
        ({"seq_slots": i32(1)} if getattr(model, "recurrent_state", False)
         else {})))
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)

    def lower(prefix_blocks: int) -> str:
        return core._step_fn.trace(
            params, jax.tree.map(sds, core.cache), sds(core._keys),
            tuple(sds(b) for b in bufs), layout=layout,
            prefix_blocks=prefix_blocks, k_cand=K_MAX, exact=False,
        ).lower(lowering_platforms=("tpu",)).as_text()

    return core, lower


@pytest.mark.parametrize("case", [
    "llama-flash", "hybrid_linear-flash", "zaya-flash", "llama-window",
    "llama-xla", "glm-flash"])
def test_prefix_blocks_keys_a_prefill_program_only_where_it_sizes_a_gather(
        monkeypatch, tpu_gate, case):
    family, dispatch = case.split("-")
    if dispatch == "xla":
        monkeypatch.setenv("DYNAMO_DISABLE_PALLAS_PREFILL", "1")
    # a window of 1,024: 16 cached blocks and the chunk fit it, 64 do not
    # (which decided the dispatch until PR 60)
    model = _keyed_toy(family, **(
        {"sliding_window": 1024} if dispatch == "window" else {}))
    core, lower = _keyed_engine(model)
    keys = [core._prefix_blocks("prefill", pb, _KEY_S) for pb in _KEY_PREFIXES]
    if family == "glm":
        # the model sizes its own context gather by the value: a program a
        # bucket, whatever its attention kernels do
        assert keys == list(_KEY_PREFIXES)
        assert lower(16) != lower(64)
    elif dispatch == "flash":
        # one jit entry for a chunk behind 0, 16 and 64 cached blocks ...
        assert keys == [0, 0, 0]
        # ... because the modules the three values lower to are one module
        modules = {lower(pb) for pb in _KEY_PREFIXES}
        assert len(modules) == 1
        assert "paged_prefill_attention" in modules.pop()
    elif dispatch == "window":
        # since PR 60 the kernel masks by the window itself at every prefix
        # (its windowed form: the table, 64 blocks and the chunk, can hold
        # a context past 1,024), so a window model too builds one program
        assert keys == [0, 0, 0]
        modules = {lower(pb) for pb in _KEY_PREFIXES}
        assert len(modules) == 1
        assert "paged_prefill_attention_window" in modules.pop()
    else:
        # the XLA form gathers ``prefix_blocks`` blocks: the bucket stays
        assert keys == list(_KEY_PREFIXES)
        assert lower(16) != lower(64)
        assert "paged_prefill_attention" not in lower(16)


# ---------------------------------------------------------------------------
# PR 60: mellum2-12b-a2.5b.  The attention kernels had only ever run at
# max_model_len <= 4,096 (128 blocks a row); this cell's tables are 32 x 1,152
# blocks and its prefixes up to 1,024 blocks, and six of its eight layers call
# the kernels' windowed form.
@pytest.mark.parametrize("program,chunk", [
    ("decode", None), ("prefill", 2048), ("prefill", 256), ("reference", 768)],
    ids=["decode", "document-chunk", "question", "reference"])
def test_mellum_cell_programs_name_both_kernel_forms_and_fit_the_chip(
        topo, tpu_gate, program, chunk):
    """The cell's decode program (32 rows, a 32 x 1,152 block table), a
    2,048-token document chunk, a question's 256-token bucket and the check's
    float32 reference over its longest sequence (700 + 8 tokens, padded to
    768), whole (8 layers = two periods, 64 experts, the 98,304-row head, the
    cell's 12,288-block pool): one scan over the two periods, each layer of
    a period with its own attention call - three of the windowed kernel's
    name and one of the full kernel's - the experts through the grouped
    matmul at K 2,304 / N 896 (4 rows an expert in decode, 32 in a question;
    a document chunk's 256 are ``ragged_dot``'s) read where they lie, the pool donated and
    written in place, and weights + K/V inside the chip with room for the
    programs' temporaries and the reference beside them."""
    hf, cfg, model, params, cache, sds = _abstract_model(
        "mellum2-12b-a2.5b.json",
        lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    nbytes = lambda a: a.size * a.dtype.itemsize
    weights = sum(nbytes(a) for a in jax.tree.leaves(params))
    held = nbytes(cache)
    assert 7.58e9 < weights < 7.60e9                          # 3.795 B x 2
    assert held == 12288 * 32 * 8 * 2048                      # 6.0 GiB
    assert cfg.period == ("sliding_attention",) * 3 + ("full_attention",)
    assert (cfg.window_layers, cfg.sliding_window) == (6, 1024)
    if program == "reference":
        from cellbench import spec

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ref = spec.load_module(root, "reference", hf["reference"])
        compiled = jax.jit(ref.make_forward(hf)).lower(
            params, sds((chunk,)), sds((8,))).compile()
        mem = compiled.memory_analysis()
        print(f"# reference: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
        # beside the served model: its weights are the reference's
        # arguments, the cache is the engine's
        assert mem.temp_size_in_bytes < 0.6e9, mem.temp_size_in_bytes
        assert (weights + held + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < 0.93 * V5E_HBM
        return
    if chunk:
        serve["prefill_chunk_tokens"] = chunk
    # on the flash path every prefix is the one program (PR 57): the engine
    # hands jit prefix_blocks 0 whatever is cached
    from dynamo_tpu.ops.paged_attention import prefill_program_key
    assert prefill_program_key(
        "prefill", 1024, chunk or 2048, cfg.sliding_window,
        num_kv_heads=cfg.num_kv_heads, block_size=BS) == 0
    fn, args = _step_program(program, model, serve, sds, prefix_blocks=0)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    stem = ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention")
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and stem in line]
    # a period's four layers, unrolled in the one scan's body
    assert len(calls) == 4, [c[:120] for c in calls]
    assert sum(f"{stem}_window" in c for c in calls) == 3
    scoped = [re.search(r'op_name="([^"]*)"', c).group(1) for c in calls]
    assert sum("/attn/window/" in s for s in scoped) == 3, scoped
    assert sum("/attn/full/" in s for s in scoped) == 1, scoped
    if chunk == 2048:   # 256 rows an expert: past the kernel's one row tile
        assert not _grouped_matmul_calls(hlo)
    else:               # gate + up, down a layer
        assert len(_grouped_matmul_calls(hlo)) == 2 * 4
        assert "ragged-dot" not in hlo
    assert hlo.count(" while(") == 1
    assert not re.search(r"bf16\[8,12288,2,32,512\]\S* copy\(", hlo)
    # no layer's expert stack is sliced out of the [8, 64, ...] arrays
    assert not re.search(r"bf16\[(4,)?64,2304,896\]\S* (copy|dynamic-slice)\(",
                         hlo)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held                    # donated
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program} {chunk}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 14.0e9 < mem.argument_size_in_bytes < 14.1e9
    assert mem.temp_size_in_bytes < 0.8e9, mem.temp_size_in_bytes
    assert total < 0.9 * V5E_HBM, total


@pytest.mark.parametrize("program", [
    "decode", "prefill",
    # 89 CPU-seconds of compiling (five scans of float32 layer bodies): over
    # the tier-1 budget of a test; the builder ran it before the first chip
    # call (temporaries 0.041 GB)
    pytest.param("reference", marks=pytest.mark.slow)])
def test_jamba_cell_programs_run_both_selective_kernels_in_place(
        topo, tpu_gate, program):
    """jamba2-3b's decode program (64 rows = the slot array), a 512-token
    chunk with 16 blocks of the prompt cached, and the check's float32
    reference over its longest sequence, whole (28 layers m x7 | A | m x13 |
    A | m x6, the 65,536-row tied matrix, the cell's pool and its 64 slots of
    state): the two attending layers through the Pallas GQA kernels at 20
    query heads on ONE K/V head of 128 (a 128-lane cache row, 20 rows a
    sequence: neither a multiple of 8 sublanes nor reached by another cell),
    the 26 Mamba layers through ops/pallas/selective_state.py — the decode
    update where the state lies and the chunk's scan, each inside the scope
    its roofline metric reads — every leaf of the cache donated and written
    in place, and weights + state + K/V inside the chip (~6.9 GB)."""
    hf, cfg, model, params, cache, sds = _abstract_model(
        "jamba2-3b.json", lambda spec: SingleDeviceSharding(topo.devices[0]))
    serve = dict(hf["serve"])
    nbytes = lambda a: a.size * a.dtype.itemsize
    weights = sum(nbytes(a) for a in jax.tree.leaves(params))
    held = sum(nbytes(a) for a in jax.tree.leaves(cache))
    assert hf["attention_layers"] == len(cfg.gqa_layers) == 2
    assert [(r.kind, r.count) for r in model.runs] == [
        ("linear", 7), ("gqa", 1), ("linear", 13), ("gqa", 1), ("linear", 6)]
    assert cache["state"].shape == (26, 64, 16, 40, 128)
    assert cache["kv"].shape == (2, 6272, 2, 32, 128)
    assert model.state_update_impl() == ("pallas", "tpu")
    assert model.state_scan_impl() == ("pallas", "tpu")
    if program == "reference":
        from cellbench import spec

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ref = spec.load_module(root, "reference", hf["reference"])
        compiled = jax.jit(ref.make_forward(hf)).lower(
            params, sds((768,)), sds((8,))).compile()
        mem = compiled.memory_analysis()
        print(f"# reference: temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
        assert mem.temp_size_in_bytes < 1.6e9, mem.temp_size_in_bytes
        assert (weights + held + mem.temp_size_in_bytes
                + mem.output_size_in_bytes) < 0.6 * V5E_HBM
        return
    fn, args = _step_program(program, model, serve, sds, prefix_blocks=16)
    if program == "prefill":        # the engine names the row's slot
        from dynamo_tpu.engine.core import unified_step

        fn = lambda p, c, *a: unified_step(
            model, p, c, *a[:-1], prefix_blocks=16, seq_slots=a[-1])
        args = (*args, sds((1,)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    hlo = compiled.as_text()
    assert ("paged_decode_attention" if program == "decode"
            else "paged_prefill_attention") in hlo
    kernel, scope = (("selective_state_update", "selective_step")
                     if program == "decode"
                     else ("selective_state_scan", "selective_scan"))
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and kernel in line]
    assert len(calls) == 3                      # m x7 | m x13 | m x6
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
        assert scope in op_name and "selective" in op_name, op_name
    other = ("selective_state_scan" if program == "decode"
             else "selective_state_update")
    assert other not in hlo
    assert "ragged-dot" not in hlo and "grouped_expert_matmul" not in hlo
    # the state is updated where it lies, never copied whole or a layer
    assert not re.search(r"f32\[26,64,16,40,128\]\S* copy\(", hlo)
    assert "f32[64,16,40,128]" not in hlo
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held                   # all donated
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"# {program}: arguments {mem.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB, "
          f"total {total / 1e9:.3f} GB")
    assert 6.8e9 < mem.argument_size_in_bytes < 7.0e9
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    assert 0.40 * V5E_HBM < total < 0.46 * V5E_HBM, total
