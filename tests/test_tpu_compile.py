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

import functools
import importlib
import os

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


def test_tp_rules_that_keep_the_xla_path(tpu_gate):
    """The static, named exceptions under a mesh — made before tracing."""
    kw = dict(num_kv_heads=8, block_size=BS)
    assert pa.attention_impl("decode", tp=4, quant=True, **kw)[0] == "xla"
    assert pa.attention_impl("decode", tp=16, **kw)[0] == "xla"
    assert pa.attention_impl("prefill", windowed=True, **kw)[0] == "xla"
    assert pa.attention_impl(
        "decode", num_kv_heads=8, block_size=16, quant=True)[0] == "xla"
