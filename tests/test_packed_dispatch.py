"""A dispatch is one transfer and one program (PR 55).

``EngineCore._upload_dispatch`` sends a dispatch's small operands as one
buffer, and the jitted serving call takes it apart itself and reads its key
out of the engine's key block (``engine/core.py::packed``): no program runs
ahead of it but ``key_block``, once in ``KEY_BLOCK`` dispatches.  Moving
the split and the unpacking changes no sample: the runs below give the
tokens the tree before the change gave (``packed_dispatch_golden.json``,
written by ``PYTHONPATH=. python tests/test_packed_dispatch.py`` on that
tree; run it again only for a change that is meant to alter samples)."""

import json
import logging
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.utils.mesh import build_mesh
from test_operand_placement import (make_core, prompt,  # the same toy
                                     taken_apart, watch)         # engine

GOLDEN = Path(__file__).with_name("packed_dispatch_golden.json")
LAYER_METRICS = Path(__file__).parents[1] / "cellbench" / "layer_metrics"

S = SamplingOptions
# what a run's four requests ask for; a row with a temperature and no seed
# is drawn from the dispatch's key, so its tokens pin the engine's key chain
TRAFFIC = {
    "sampling": [S(temperature=0.7, top_p=0.9, min_p=0.05),
                 S(temperature=1.0, seed=5, logit_bias={7: 2.5, 11: -3.0}),
                 S(temperature=0.9, top_k=40),
                 S(temperature=0.8, top_p=0.9, seed=1234)],
    "grammar": [S(temperature=1.0, json_mode=True),
                S(temperature=1.0, seed=5, json_mode=True),
                S(temperature=0.0),
                S(temperature=0.9)],
    "penalties": [S(temperature=0.8, frequency_penalty=0.6),
                  S(temperature=0.9, presence_penalty=0.8, seed=3),
                  S(temperature=0.0, frequency_penalty=0.3,
                    presence_penalty=0.2),
                  S(temperature=1.0)],
}
CHUNKED = dict(prefill_chunk_tokens=16)
# run -> (traffic, engine, mesh shape): the three kinds of row through the
# plain engine on one device and on the (1, 4) mesh, then each of the other
# jitted entry points (no benchmark cell runs those)
RUNS = {
    "sampling": ("sampling", CHUNKED, None),
    "sampling-tp4": ("sampling", CHUNKED, (1, 4)),
    "grammar": ("grammar", CHUNKED, None),
    "grammar-tp4": ("grammar", CHUNKED, (1, 4)),
    "penalties": ("penalties", CHUNKED, None),
    "penalties-tp4": ("penalties", CHUNKED, (1, 4)),
    "ragged": ("sampling", dict(**CHUNKED, prefill_token_budget=64), None),
    "unified": ("penalties", dict(**CHUNKED, prefill_token_budget=64,
                                  unified_token_dispatch=True), None),
    "spec": ("sampling", dict(spec_tokens=2), None),
    "seq-parallel": ("sampling", dict(sp_prefill_threshold=16), (2, 2)),
}
REACHES = {"sampling": "_multi_fn", "ragged": "_ragged_fn",
           "unified": "_unified_fn", "spec": "_spec_fn",
           "seq-parallel": "_sp_fn"}
ENTRY_POINTS = ("_step_fn", "_multi_fn", "_spec_fn", "_ragged_fn",
                "_unified_fn", "_sp_fn")


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def mesh_of(shape):
    if shape is None:
        return None
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        pytest.skip("needs the virtual multi-device CPU mesh")
    return build_mesh(shape, devices=jax.devices()[:n])


def serve(core, samplings):
    """Two requests, then two more while the first decode: chunked
    prefills, decodes that carry a sample and decodes that do not.
    Returns each request's tokens."""
    toks = {f"r{i}": [] for i in range(4)}
    ended = []

    def emit(out, rid):
        toks[rid].extend(int(t) for t in out.token_ids)
        if out.finish_reason is not None:
            ended.append(rid)

    for first in (0, 2):
        for i in (first, first + 1):
            core.submit(EngineRequest(
                f"r{i}", prompt(40 - 9 * i, i), samplings[i],
                StopConditions(max_tokens=8 + 3 * i, ignore_eos=not
                               samplings[i].json_mode),
                lambda out, rid=f"r{i}": emit(out, rid)))
        for _ in range(7):
            core.step()
    while core.step():
        pass
    assert sorted(ended) == sorted(toks)
    return toks


def run(tiny, name):
    traffic, cfg, shape = RUNS[name]
    core = make_core(tiny, mesh_of(shape), **cfg)
    toks = serve(core, TRAFFIC[traffic])
    return core, toks


@pytest.mark.parametrize("name", sorted(RUNS))
def test_tokens_are_those_of_the_tree_before(tiny, name):
    core, toks = run(tiny, name)
    assert toks == json.loads(GOLDEN.read_text())[name]
    # ... and came through the entry point the run was meant to reach
    assert getattr(core, REACHES.get(name, "_step_fn"))._cache_size() > 0


# ------------------------------------------------- one program a dispatch
def programs_built(caplog):
    """Names of the jitted functions compiled while ``caplog`` listened
    under ``jax.log_compiles``."""
    return {m.group(1) for r in caplog.records
            if (m := re.match(r"Compiling (\S+) with global shapes",
                              r.getMessage()))}


@pytest.mark.parametrize("tp", [1, 4])
def test_no_program_runs_ahead_of_a_serving_call(tiny, tp, caplog):
    """A warm-up builds the serving programs and nothing beside them: no
    ``operand_prologue``, no eager ``random.split`` and no slice of its
    result, on one device as on the mesh (``key_block``, the one program
    that runs ahead of a serving call, once in ``KEY_BLOCK`` dispatches,
    was built with the engine)."""
    core = make_core(tiny, mesh_of((1, 4)) if tp > 1 else None, **CHUNKED)
    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        serve(core, TRAFFIC["grammar"])
    # the tree before built jit(_threefry_split) and jit(_unstack) beside
    # these on one device, jit(operand_prologue) on the mesh
    assert programs_built(caplog) == {"jit(_step_impl)", "jit(_multi_impl)"}


def module_patterns():
    """The regular expressions by which the benchmark finds a serving
    program in a profile (cellbench/layer_metrics/device.*_program_ms)."""
    files = sorted(LAYER_METRICS.glob("device.*_program_ms.json"))
    assert len(files) == 2
    return [re.compile(json.loads(f.read_text())["args"]["pattern"])
            for f in files]


@pytest.mark.parametrize("attr", ENTRY_POINTS)
def test_the_compiled_module_keeps_its_name(tiny, attr):
    """The jitted entry point is still named for its impl, so its module
    is ``jit__<kind>_impl(<id>)`` on a profile's ``XLA Modules`` line."""
    cfg = (dict(sp_prefill_threshold=16), (2, 2)) if attr == "_sp_fn" else (
        {}, None)
    core = make_core(tiny, mesh_of(cfg[1]), **cfg[0])
    kind = attr[1:-3]
    module = "jit_" + getattr(core, attr).__wrapped__.__name__
    assert module == f"jit__{kind}_impl"
    found = [p for p in module_patterns() if p.search(module + "(7)")]
    # the unified program is in neither class (no cell runs it)
    assert len(found) == (0 if kind == "unified" else 1)


def test_a_lowered_call_is_named_for_its_impl(tiny):
    """... and what the compiler is handed carries that name."""
    core = make_core(tiny, None, **CHUNKED)
    seen = []
    fn = core._multi_fn

    def watched(*args, **kw):
        seen.append(fn.lower(*args, **kw).as_text().splitlines()[0])
        return fn(*args, **kw)

    core._multi_fn = watched
    serve(core, TRAFFIC["sampling"])
    assert seen and all("@jit__multi_impl " in line for line in seen), seen[:1]


# ------------------------------------------- one layout an operand signature
def signature(args, kw):
    """What keyed a serving executable before the operands were packed:
    (shape and dtype of every operand by place — ``None``: the key's — and
    by keyword, the statics and the device arrays beside the buffer)."""
    ops, ops_kw = taken_apart(args, kw)
    leaf = lambda a: None if a is None else (a.shape, str(a.dtype))
    rest = {k: v if isinstance(v, (bool, int, type(None))) else
            jax.tree.map(leaf, v) for k, v in kw.items() if k != "layout"}
    return ((tuple(map(leaf, ops)),
             tuple(sorted((k, leaf(v)) for k, v in ops_kw.items()))),
            repr(sorted(rest.items())))


@pytest.mark.parametrize("tp", [1, 4])
def test_no_operand_shape_is_served_under_two_layouts(tiny, tp):
    """The set-up finding of PR 55: ``layout`` is a function of the
    operands' shapes and keys and of nothing else, so a warm-up of every
    prompt shape and a mixed run of chunked prefills and decodes (rows that
    carry a sample and rows that do not, a grammar row, seeded rows,
    extras that come and go) call each entry point with as many layouts as
    operand signatures, and jit holds one executable for each: no shape is
    traced, loaded or compiled twice."""
    core = make_core(tiny, mesh_of((1, 4)) if tp > 1 else None, **CHUNKED)
    fns = {name: getattr(core, name) for name in ("_step_fn", "_multi_fn")}
    calls = {name: watch(core, name) for name in fns}
    for i, n in enumerate((5, 16, 17, 40, 64, 100)):     # the warm-up
        core.submit(EngineRequest(
            f"w{i}", prompt(n, 50 + i), S(temperature=0.9, top_p=0.9),
            StopConditions(max_tokens=2, ignore_eos=True), lambda out: None))
        while core.step():
            pass
    serve(core, TRAFFIC["grammar"])
    serve(core, TRAFFIC["sampling"])
    for name, seen in calls.items():
        assert len(seen) >= 12
        by_layout, by_shapes, programs = {}, {}, set()
        for args, kw in seen:
            shapes, rest = signature(args, kw)
            by_layout.setdefault(kw["layout"], set()).add(shapes)
            by_shapes.setdefault(shapes, set()).add(kw["layout"])
            programs.add((shapes, rest))
        assert all(len(shapes) == 1 for shapes in by_layout.values()), name
        assert all(len(layouts) == 1 for layouts in by_shapes.values()), name
        assert len(by_layout) == len(by_shapes) >= 2
        # ... and the key block, a result of one program and an argument
        # of the next 256, never makes jit take an old signature for a new
        # one
        assert fns[name]._cache_size() == len(programs), name


# ------------------------------------- a prefill program a (bucket, prefix)
# PR 57: what keys a prefill program is the dispatch rule's to say
# (``EngineCore._prefix_blocks``).  Here, under XLA, the cached prefix sizes a
# gather and keeps its power-of-two bucket; the runs below hold the tokens of
# a prompt of four chunks and of one that finds 40 of its tokens cached to the
# tree before (the golden file's ``prefix-*`` entries), through each of the
# three prefill entry points.
# run -> (the entry point watched, the engine, the ``prefix_blocks`` it is
# handed: the chunks behind 0, 2, 4 and 6 blocks and the hit's behind 5 and
# 7; the unified dispatch takes the hit's chunks beside the first request's
# decode row, whose 9 blocks of context bound the gather)
PREFIX_RUNS = {
    "prefix-step": ("_step_fn", CHUNKED, {0, 2, 4, 8}),
    "prefix-ragged": ("_ragged_fn", dict(**CHUNKED, prefill_token_budget=64),
                      {0, 2, 4, 8}),
    "prefix-unified": ("_unified_fn", dict(
        **CHUNKED, prefill_token_budget=64, unified_token_dispatch=True),
        {16}),
}


def serve_prefix(core):
    """A 64-token prompt (four chunks of 16: behind 0, 2, 4 and 6 cached
    blocks of 8), a few decodes, and while it decodes a prompt whose first
    40 tokens are the first one's, so that its first chunk goes out behind
    5 cached blocks.  Returns each request's tokens."""
    toks = {"long": [], "hit": []}
    first = prompt(64, 7)
    asks = {"long": (first, S(temperature=0.7, top_p=0.9)),
            "hit": (first[:40] + prompt(20, 8), S(temperature=0.0))}
    for rid, (tokens, sampling) in asks.items():
        core.submit(EngineRequest(
            rid, tokens, sampling,
            StopConditions(max_tokens=6, ignore_eos=True),
            lambda out, rid=rid: toks[rid].extend(
                int(t) for t in out.token_ids)))
        for _ in range(6):
            core.step()
    while core.step():
        pass
    return toks


def run_prefix(tiny, name):
    attr, cfg, _ = PREFIX_RUNS[name]
    core = make_core(tiny, None, **cfg)
    held = [getattr(core, run[0]) for run in PREFIX_RUNS.values()]
    calls = watch(core, attr)
    return core, calls, serve_prefix(core), held


@pytest.mark.parametrize("name", sorted(PREFIX_RUNS))
def test_chunks_behind_a_cached_prefix_give_the_tokens_of_the_tree_before(
        tiny, name):
    core, calls, toks, held = run_prefix(tiny, name)
    assert toks == json.loads(GOLDEN.read_text())[name]
    m = core.metrics()
    assert m["prompt_tokens_cached_total"] == 40          # the hit was one
    # the XLA form gathers the prefix: every bucket of it keys a program
    keyed = {kw["prefix_blocks"] for _, kw in calls}
    assert keyed == PREFIX_RUNS[name][2]
    # ... and the count of them is what the jit caches hold
    assert m["prefill_programs_total"] == sum(
        fn._cache_size() for fn in held) >= len(keyed)


if __name__ == "__main__":
    from dynamo_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(8)        # as tests/conftest.py does
    model = LlamaModel(ModelConfig.tiny())
    weights = model, model.init_params(jax.random.PRNGKey(0))
    golden = {name: run(weights, name)[1] for name in RUNS}
    golden.update((name, run_prefix(weights, name)[2]) for name in PREFIX_RUNS)
    GOLDEN.write_text("{\n" + ",\n".join(
        f' "{name}": {json.dumps(golden[name])}'
        for name in sorted(golden)) + "\n}\n")
    print(GOLDEN.read_text())
