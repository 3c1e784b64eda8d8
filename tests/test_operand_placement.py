"""A dispatch's operands arrive where the program wants them (PRs 32, 33).

Under a mesh ``EngineCore._upload_dispatch`` sends a dispatch's small
operands as two buffers (``engine/operands.py``), puts them once, from the
host, to the replicated sharding of the engine's mesh, and has one small
program take them apart there and draw the dispatch's key; the carry and
the grammar tables live in the same layout: a jitted serving call then
finds every operand committed as its executable was compiled for and
re-lays nothing out (on four chips that re-layout, inside every call, was
3 ms of a 25 ms turn with the devices idle).  With no mesh the operands are
what they always were: the plain put of the tree, uncommitted, on the
default device, and the key split on the host's side of the call."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from dynamo_tpu.engine import EngineConfig, EngineCore, operands
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
# where the key sits among its operands (after params and cache)
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
    """Every array a watched call was handed, and nothing but arrays and
    the Python scalars of its static arguments."""
    for args, kw, *_ in calls:
        leaves = jax.tree.leaves((args, kw))
        assert len(leaves) >= 10
        for leaf in leaves:
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
    assert all(args[at].dtype == np.uint32 for args, _ in calls)
    kws = set().union(*(kw for _, kw in calls))
    if case == "decode":
        # a decode with nothing to carry and one that carries: one layout
        assert {bool(kw["carry_rows"].any()) for _, kw in calls} == {
            False, True}
        assert "carry_tokens" in kws
    if case != "ragged":    # the ragged engine's final chunks ride _step_fn
        assert {"grammar", "seeds", "min_p"} <= kws


@pytest.mark.parametrize("case", ["prefill", "decode"])
def test_with_no_mesh_the_operands_are_where_they_were(tiny, case):
    name, cfg, _ = CASES[case]
    core = make_core(tiny, None, **cfg)
    calls = watch(core, name)
    serve(core)
    assert len(calls) >= 3
    default = SingleDeviceSharding(jax.devices()[0])
    for a in arrived(calls):
        assert not a.committed and a.sharding == default


@pytest.mark.parametrize("case", ["decode", "ragged", "unified"])
@pytest.mark.parametrize("tp", [1, 4])
def test_the_key_sequence_is_one_split_a_dispatch(tiny, tp, case, request):
    """Placing the key moves it, it does not change it, and neither does
    splitting it in one program: dispatch by dispatch, on four devices as
    on one, the keys are ``jax.random.split``'s chain from the seed."""
    where = request.getfixturevalue("mesh") if tp > 1 else None
    core = make_core(tiny, where, **CASES[case][1])
    calls = []
    for name, _, at in CASES.values():
        watch(core, name, calls, at)
    serve(core)
    assert len(calls) >= 12
    chain = jax.random.PRNGKey(core.config.seed)
    for args, _, at in calls:
        chain, key = jax.random.split(chain)
        np.testing.assert_array_equal(np.asarray(args[at]), np.asarray(key))


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
    # under a mesh a dispatch uploads two buffers (its nine small arrays
    # packed by dtype: one program on the devices takes them apart), each
    # to every device; with no mesh the nine arrays as they are
    per_dispatch = 2 * tp if tp > 1 else 9
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
    for args, _ in calls:
        lens = np.asarray(args[3])        # seq_lens, by slot
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


# ------------------------------------------- the two buffers, taken apart
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
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_pack_and_unpack_give_the_tree_back(name):
    tree = TREES[name]
    (ints, floats, *others), layout = operands.pack(tree)
    assert ints.dtype == np.int32 and floats.dtype == np.float32
    assert ints.ndim == floats.ndim == 1 and hash(layout) == hash(layout)
    packed = {np.dtype(np.int32), np.dtype(bool), np.dtype(np.float32)}
    assert all(o.dtype not in packed for o in others)
    back = jax.jit(operands.unpack, static_argnames="layout")(
        (ints, floats, *others), layout=layout)
    want, got = jax.tree.flatten(tree), jax.tree.flatten(back)
    assert want[1] == got[1]
    for a, b in zip(want[0], got[0], strict=True):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, np.asarray(b))
