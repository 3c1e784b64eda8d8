"""A dispatch's operands arrive where the program wants them, in one
transfer (PRs 32, 33, 55).

``EngineCore._upload_dispatch`` sends a dispatch's small operands as one
int32 buffer (``engine/operands.py``), put once, from the host: to the
replicated sharding of the engine's mesh, or with no mesh to the default
device.  The jitted serving call takes the buffer apart itself
(``engine/core.py::packed``) and reads its key out of the engine's key
block, ``jax.random.split``'s chain drawn ``KEY_BLOCK`` dispatches at a
time, at the place the buffer says; the block, the carry and the grammar
tables live in the same layout: a call finds every operand committed as its
executable was compiled for and re-lays nothing out (on four chips that
re-layout, inside every call, was 3 ms of a 25 ms turn with the devices
idle), and no program runs ahead of it but the block's, once in
``KEY_BLOCK`` dispatches."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from dynamo_tpu.engine import EngineConfig, EngineCore, operands
from dynamo_tpu.engine import core as engine_core
from dynamo_tpu.engine import counters as engine_counters
from dynamo_tpu.engine.grammar import JsonGrammar
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.http.metrics import Metrics
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.utils.mesh import build_mesh

EOS = 2
# the jitted entry point a case watches, the engine that reaches it, and
# where its impl takes the dispatch's key among its operands (after params
# and cache): ``None`` there in what the buffer unpacks to
CASES = {
    "prefill": ("_step_fn", dict(prefill_chunk_tokens=16), 6),
    "decode": ("_multi_fn", dict(prefill_chunk_tokens=16), 5),
    "ragged": ("_ragged_fn", dict(prefill_chunk_tokens=16,
                                  prefill_token_budget=64), 9),
    "unified": ("_unified_fn", dict(prefill_chunk_tokens=16,
                                    prefill_token_budget=64,
                                    unified_token_dispatch=True), 9),
}


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual multi-device CPU mesh")
    return build_mesh((1, 4), devices=jax.devices()[:4])


def json_grammar(vocab_size):
    toks = [None] * vocab_size
    for b in range(min(256, vocab_size - 3)):
        toks[3 + b] = bytes([b])
    return JsonGrammar.from_token_bytes(toks, eos_ids=[EOS])


def make_core(tiny, mesh, **kw):
    return EngineCore(*tiny, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
        prefill_buckets=[16, 32, 64, 128], **kw), mesh=mesh,
        eos_token_ids=[EOS], grammar=json_grammar(tiny[0].config.vocab_size))


def watch(core, name, calls=None, *tag):
    """Record what every call of the jitted ``core.<name>`` is handed
    besides ``params`` and ``cache``: (positional operands, keywords)."""
    calls = [] if calls is None else calls
    fn = getattr(core, name)

    def watched(params, cache, *args, **kw):
        calls.append((args, kw, *tag))
        return fn(params, cache, *args, **kw)

    setattr(core, name, watched)
    return calls


def taken_apart(args, kw):
    """(positional operands, keyword operands) of a watched call, as the
    program unpacks them from its one buffer."""
    _, bufs = args
    return operands.unpack(jax.device_get(bufs), kw["layout"])[1:]


def key_of(args, kw):
    """The key a watched call hands its impl: the one of the engine's key
    block that the buffer's first word names."""
    keys, bufs = args
    return np.asarray(keys)[int(np.asarray(bufs[0])[0])]


def prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(3, 200, size=n)]


def serve(core):
    """A chunked prefill and a few decodes of each kind of row: unseeded
    (the dispatch's key draws it), grammar-bound, plain, seeded.  The
    second pair joins while the first decodes (a unified engine mixes the
    two phases in one dispatch then); the grammar row holds the engine to
    the serial step, and once it has ended every decode carries a sample."""
    samplings = [SamplingOptions(temperature=0.7, top_p=0.9, min_p=0.05),
                 SamplingOptions(temperature=1.0, seed=5, json_mode=True),
                 SamplingOptions(temperature=0.0),
                 SamplingOptions(temperature=0.8, top_p=0.9, seed=1234)]
    outs = []
    for first in (0, 2):
        for i in (first, first + 1):
            core.submit(EngineRequest(
                f"r{i}", prompt(40 - 9 * i, i), samplings[i],
                StopConditions(max_tokens=8 + 3 * i, ignore_eos=i != 1),
                outs.append))
        for _ in range(7):
            core.step()
    while core.step():
        pass
    assert sum(o.finish_reason is not None for o in outs) == 4


def arrived(calls):
    """Every array a watched call was handed — the key block, ONE int32
    buffer, and what lives on the device already (the carry, the grammar's
    tables) — and nothing but arrays and the Python scalars of its static
    arguments."""
    for args, kw, *_ in calls:
        keys, bufs = args
        assert keys.dtype == np.uint32
        assert keys.shape == (engine_core.KEY_BLOCK, 2)
        assert len(bufs) == 1
        assert bufs[0].dtype == np.int32 and bufs[0].ndim == 1
        ops, ops_kw = taken_apart(args, kw)
        assert len(ops) + len(ops_kw) >= 10
        kw = {k: v for k, v in kw.items() if k != "layout"}
        for leaf in jax.tree.leaves((args, kw)):
            if isinstance(leaf, (bool, int)):
                continue            # prefix_blocks, k_cand, exact, ...
            # a host array here would be uploaded inside the call
            assert isinstance(leaf, jax.Array), type(leaf)
            yield leaf


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_operand_arrives_replicated_over_the_mesh(tiny, mesh, case):
    name, cfg, at = CASES[case]
    core = make_core(tiny, mesh, **cfg)
    calls = watch(core, name)
    serve(core)
    assert len(calls) >= 3
    replicated = NamedSharding(mesh, PartitionSpec())
    for a in arrived(calls):
        assert a.committed and a.sharding.is_equivalent_to(
            replicated, a.ndim), (a.shape, a.sharding)
        assert len(a.addressable_shards) == 4
    apart = [taken_apart(args, kw) for args, kw in calls]
    assert all([a is None for a in ops] == [i == at for i in range(len(ops))]
               for ops, _ in apart)
    kws = set().union(*(kw for _, kw in calls),
                      *(ops_kw for _, ops_kw in apart))
    if case == "decode":
        # a decode with nothing to carry and one that carries: one layout
        assert {bool(ops_kw["carry_rows"].any()) for _, ops_kw in apart} == {
            False, True}
        assert "carry_tokens" in kws
    if case != "ragged":    # the ragged engine's final chunks ride _step_fn
        assert {"grammar", "seeds", "min_p"} <= kws


@pytest.mark.parametrize("case", ["prefill", "decode"])
def test_with_no_mesh_it_is_one_buffer_a_dispatch_on_the_default_device(
        tiny, case):
    name, cfg, at = CASES[case]
    core = make_core(tiny, None, **cfg)
    calls = watch(core, name)
    serve(core)
    assert len(calls) >= 3
    default = SingleDeviceSharding(jax.devices()[0])
    for a in arrived(calls):
        assert not a.committed and a.sharding == default
    assert all(taken_apart(args, kw)[0][at] is None for args, kw in calls)


@pytest.mark.parametrize("case", ["decode", "ragged", "unified"])
@pytest.mark.parametrize("tp", [1, 4])
def test_the_key_sequence_is_one_split_a_dispatch(tiny, tp, case, request,
                                                  monkeypatch):
    """Placing the keys moves them, it does not change them, and neither
    does drawing them a block at a time: dispatch by dispatch, on four
    devices as on one, through chunked prefills and decodes that carry a
    sample and over several refills of the block, the keys are
    ``jax.random.split``'s chain from the seed, as they were when the host
    split one a dispatch."""
    monkeypatch.setattr(engine_core, "KEY_BLOCK", 5)
    where = request.getfixturevalue("mesh") if tp > 1 else None
    core = make_core(tiny, where, **CASES[case][1])
    calls = []
    for name, _, at in CASES.values():
        watch(core, name, calls, at)
    serve(core)
    assert len(calls) >= 12
    chain = jax.random.PRNGKey(core.config.seed)
    # what an impl is handed, by a stand-in that gives its operands back
    probe = engine_core.packed(lambda params, cache, *ops, **kw: ops)
    for n, (args, kw, at) in enumerate(calls):
        assert args[0].shape == (5, 2)
        chain, key = jax.random.split(chain)
        np.testing.assert_array_equal(key_of(args, kw), np.asarray(key))
        if n < 7:
            ops = probe(None, None, *args, layout=kw["layout"])
            np.testing.assert_array_equal(np.asarray(ops[at]),
                                          np.asarray(key))
    # the chain stands where the last block drawn left it
    blocks = -(-len(calls) // 5)
    for _ in range(5 * blocks - len(calls)):
        chain, _ = jax.random.split(chain)
    np.testing.assert_array_equal(np.asarray(core._rng), np.asarray(chain))


@pytest.mark.parametrize("tp", [1, 4])
def test_operand_buffers_are_counted_on_metrics_and_on_the_http_render(
        tiny, tp, request):
    where = request.getfixturevalue("mesh") if tp > 1 else None
    engine_counters.reset()
    core = make_core(tiny, where)
    core.submit(EngineRequest(
        "a", prompt(8, 0), SamplingOptions(temperature=0.0),
        StopConditions(max_tokens=9, ignore_eos=True), lambda o: None))
    while core.step():
        pass
    m = core.metrics()
    dispatches = m["prefill_dispatches_total"] + m["decode_dispatches_total"]
    assert dispatches == 1 + 8
    # a dispatch uploads one buffer (its nine small arrays packed: the
    # serving program takes them apart), to every device of the mesh
    per_dispatch = tp
    assert m["operand_buffers_total"] == per_dispatch * dispatches
    assert (f"dynamo_tpu_engine_operand_buffers_total "
            f"{per_dispatch * dispatches}\n" in Metrics().render() + "\n")


@pytest.mark.parametrize("tp", [1, 4])
def test_decode_kv_blocks_are_counted_on_metrics_and_on_the_http_render(
        tiny, tp, request):
    """PR 40: the K/V blocks the rows of every decode dispatch own (what
    the flash-decode kernel fetches a layer) beside what the same dispatch
    fetched when every slot went up to its group's longest row."""
    where = request.getfixturevalue("mesh") if tp > 1 else None
    engine_counters.reset()
    core = make_core(tiny, where)
    calls = watch(core, "_multi_fn")
    for i, n in enumerate((8, 40)):       # two rows of unlike length, 4 slots
        core.submit(EngineRequest(
            f"r{i}", prompt(n, i), SamplingOptions(temperature=0.0),
            StopConditions(max_tokens=9, ignore_eos=True), lambda o: None))
    while core.step():
        pass
    m = core.metrics()
    assert len(calls) == m["decode_dispatches_total"] >= 8
    bs, walked, bound = core.config.block_size, 0, 0
    # the kernel's tiling at the tiny geometry: one group of the 4 slots,
    # a chunk as long as the rule's cap or the table
    c = min(core._decode_tiling[1], core.config.max_blocks_per_seq)
    assert core._decode_tiling[0] >= 4 and c > 1
    for args, kw in calls:
        lens = np.asarray(taken_apart(args, kw)[0][3])  # seq_lens, by slot
        assert lens.shape == (4,) and (lens > 0).sum() in (1, 2)
        blocks = -(-lens // bs)
        walked += int(blocks.sum())
        # every slot fetched in chunks of C blocks up to the longest row
        bound += 4 * c * -(-int(blocks.max()) // c)
    assert m["decode_kv_blocks_walked_total"] == walked > 0
    assert m["decode_kv_blocks_group_bound_total"] == bound > 2 * walked
    text = Metrics().render() + "\n"
    assert f"dynamo_tpu_engine_decode_kv_blocks_walked_total {walked}\n" in text
    assert (f"dynamo_tpu_engine_decode_kv_blocks_group_bound_total {bound}\n"
            in text)


# -------------------------------------------- the one buffer, taken apart
F32 = np.float32
TREES = {
    "a decode": ((np.arange(4, dtype=np.int32),
                  np.arange(8, dtype=np.int32).reshape(4, 2),
                  np.linspace(0, 1, 4, dtype=np.float32)),
                 {"carry_rows": np.array([True, False, True, False]),
                  "min_p": np.full(4, 0.05, np.float32)}),
    "no float": ((np.arange(3, dtype=np.int32),), {}),
    "other dtypes ride beside": ((np.arange(3, dtype=np.uint32),
                                 np.zeros((2, 0), np.int32),
                                 np.ones(2, np.float16)),
                                {"seeds": np.arange(2, dtype=np.uint8)}),
    # a float32 travels as its bits: values that an arithmetic round trip
    # (or a compare-and-rebuild) would not give back
    "floats whose bits matter": ((np.array(
        [-0.0, 0.0, 1e-45, -1e-45, 1.1754942e-38, np.inf, -np.inf, np.nan,
         0.1, -3.4028235e38], F32),
        np.array([0x7FC00001, 0xFFC12345, 0x00000001, 0x80000000],
                 np.uint32).view(F32).reshape(2, 2)), {}),
    "int, bool and float mixed": ((np.array([[-1, 2**31 - 1, -2**31]],
                                            np.int32),
                                   None,
                                   np.array([0.7, -0.0], F32),
                                   np.array([[True], [False]]),
                                   np.zeros((0,), F32)),
                                  {"top_p": np.array(0.9, F32),
                                   "rows": np.array([False, True, True]),
                                   "seeds": np.array([5, 0, 1234],
                                                     np.int32)}),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_pack_and_unpack_give_the_tree_back(name):
    tree = TREES[name]
    (buf, *others), layout = operands.pack(tree)
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert hash(layout) == hash(layout)
    packed_dtypes = {np.dtype(np.int32), np.dtype(bool), np.dtype(F32)}
    leaves = jax.tree.leaves(tree)
    assert all(o.dtype not in packed_dtypes for o in others)
    assert len(others) == sum(a.dtype not in packed_dtypes for a in leaves)
    assert buf.size == sum(a.size for a in leaves
                           if a.dtype in packed_dtypes)
    back = jax.jit(operands.unpack, static_argnames="layout")(
        (buf, *others), layout=layout)
    want, got = jax.tree.flatten(tree), jax.tree.flatten(back)
    assert want[1] == got[1]
    for a, b in zip(want[0], got[0], strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        # bit for bit: -0.0, a denormal, a NaN's payload
        assert a.tobytes() == np.asarray(b).tobytes()
