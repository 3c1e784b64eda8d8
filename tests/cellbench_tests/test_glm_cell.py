"""What the GLM-5.2 cell adds to the benchmark, as new files alone: three
cost modules, a traffic generator over shared documents, two readers, four
per-layer metrics, a reference, a configuration and a cell — and a tiny
rehearsal of generator + model + reference end to end in a copied root."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "glm-5.2-ep16.docs-shared-closed"
NEW_METRICS = ("kernel.indexer_roofline", "device.indexer_pct",
               "kv.prefix_hit_pct", "attn.selected_pct")
GLM = {"num_hidden_layers": 5, "num_attention_heads": 64, "kv_lora_rank": 512,
       "qk_rope_head_dim": 64, "index_topk": 2048, "index_n_heads": 32,
       "index_head_dim": 128, "dtype": "bfloat16",
       "indexer_types": ["full", "full", "shared", "shared", "shared"],
       "serve": {"prefill_chunk_tokens": 2048}}


def cost(name):
    return spec.load_module(roots.REPO, "costs", name)


# ------------------------------------------------------------ cost modules --
def test_decode_cost_is_the_hand_count():
    """Two rows at contexts 1,000 and 24,000: 1,000 + 2,048 selected rows;
    per layer and row 64 heads x 2 x (576 + 512) operations and 576 bf16
    elements; per call 64 x (576 + 512) elements of query and output."""
    ops, nbytes = cost("mla_sparse_decode").cost(GLM, [1000, 24000])
    assert ops == 5 * 64 * 2 * (576 + 512) * 3048
    assert nbytes == 5 * 2 * (576 * 3048 + 64 * (576 + 512) * 2)
    per_row = cost("mla_sparse_decode").cost(GLM, [24000])
    assert per_row[1] == pytest.approx(5 * 2048 * 1152, rel=0.07)


@pytest.mark.parametrize("take,prefix,want", [
    (3, 0, 1 + 2 + 3), (4, 10, 11 + 12 + 13 + 14),
    (100, 24576, 100 * 2048), (4, 2046, 2047 + 2048 + 2048 + 2048)])
def test_prefill_rows_are_counted_query_by_query(take, prefix, want):
    mod = cost("mla_sparse_prefill")
    assert mod.rows(take, prefix, 2048) == want
    assert want == sum(min(prefix + i + 1, 2048) for i in range(take))


def test_prefill_cost_counts_only_the_question():
    mod = cost("mla_sparse_prefill")
    assert mod.computed(24576 + 130) == (130, 24576)
    assert mod.computed(700) == (700, 0)
    assert mod.chunks(16384 + 200, 2048) == [(200, 16384)]
    assert mod.chunks(5000, 2048) == [(2048, 0), (2048, 2048), (904, 4096)]
    records = [{"prompt_len": 24576 + 130, "first": 1.5},
               {"prompt_len": 16384 + 64, "first": 9.0}]
    calls = mod.calls(records, (1.0, 2.0), GLM)
    assert calls == [(130, 24576)]
    ops, nbytes = mod.cost(GLM, calls)
    assert ops == 5 * 64 * 2 * (576 + 512) * 130 * 2048
    assert nbytes == 5 * 2 * (576 * 130 * 2048 + 64 * (576 + 512) * 130)


def test_indexer_cost_is_the_hand_count():
    mod = cost("dsa_indexer")
    ops, nbytes = mod.cost(GLM, [("d", 20000), ("p", 100, 16384)])
    pairs = 20000 + 100 * 16384 + 100 * 101 // 2
    assert ops == 2 * 2 * 32 * 128 * pairs          # two full layers
    assert nbytes == 2 * 2 * 128 * (20000 + 16484)
    records = [{"prompt_len": 16384 + 100, "first": 1.2,
                "token_times": [1.2, 1.4, 2.5]}]
    assert mod.calls(records, (1.0, 2.0), GLM) == [
        ("d", 16485), ("p", 100, 16384)]


# --------------------------------------------------------------- generator --
@pytest.fixture(scope="module")
def gen():
    return spec.load_module(roots.REPO, "generators", "shared_docs")


@pytest.fixture(scope="module")
def traffic():
    return spec.read_json(roots.REPO / "cellbench/traffic/docs-shared-closed.json")


def test_generator_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); from cellbench import spec; "
            "g = spec.load_module(%r, 'generators', 'shared_docs'); "
            "s = g.Schedule(%r, 7, 51.0, 19360); s.request(0); "
            "print('jax' in sys.modules)") % (
        str(roots.REPO), str(roots.REPO),
        {"loop": "closed", "clients": 2, "population_seed": 0,
         "documents": {"lengths": [8192], "each": 2},
         "question_len": {"dist": "uniform", "min": 4, "max": 9},
         "output_len": {"dist": "fixed", "value": 3}})
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.stdout.strip() == "False", out.stderr[-1000:]


def test_generator_offers_the_stated_traffic(gen, traffic):
    s = gen.Schedule(traffic, 2**31 + 5, 51.0, 19360)
    assert (s.loop, s.clients, s.ramp_s, s.n) == ("closed", 32, 30.0, None)
    assert s.sampling == {"temperature": 0.7, "top_p": 0.9}
    assert sorted(n for n, _ in s.documents) == (
        [16384] * 4 + [24576] * 4 + [32768] * 4)
    assert len(set(s.documents)) == 12
    for p, o in s.sizes:
        assert p % 8192 in range(64, 257) and p // 8192 in (2, 3, 4)
        assert 32 <= o <= 384
    answers = sorted(o for _, o in s.sizes)
    assert 110 <= answers[len(answers) // 2] <= 150
    # sizes come from the population seed alone: every seed offers the same
    assert s.sizes == gen.Schedule(traffic, 3, 51.0, 19360).sizes


def test_first_twelve_requests_are_the_twelve_first_asks(gen, traffic):
    small = {**traffic, "documents": {"lengths": [8192, 16384], "each": 2},
             "question_len": {"dist": "uniform", "min": 5, "max": 9}}
    s = gen.Schedule(small, 11, 5.0, 500)
    reqs = [s.request(k) for k in range(9)]
    docs = [tuple(r.prompt[:len(r.prompt) // 8192 * 8192]) for r in reqs]
    assert len(set(docs[:4])) == 4                  # four first asks
    assert docs[4:8] == docs[:4] and docs[8] == docs[0]
    tails = [tuple(r.prompt[len(d):]) for r, d in zip(reqs, docs)]
    assert len(set(tails)) == 9 and all(5 <= len(t) <= 9 for t in tails)
    assert all(0 < t < 500 for r in reqs for t in r.prompt)
    assert [r.max_tokens for r in reqs] == [o for _, o in s.sizes[:9]]
    # the same seed gives the same documents, another seed others
    again = gen.Schedule(small, 11, 5.0, 500).request(4)
    assert again.prompt == reqs[4].prompt
    assert gen.Schedule(small, 12, 5.0, 500).request(0).prompt != reqs[0].prompt


def test_warm_up_prompts_are_documents_of_the_schedule(gen, traffic):
    small = {**traffic, "documents": {"lengths": [8192], "each": 4}}
    s = gen.Schedule(small, 5, 5.0, 300)
    warm = gen.prompt_ids(5, -2, 8192 + 70, 300)
    assert len(warm) == 8262 and warm[:8192] == s.request(1).prompt[:8192]
    check = gen.prompt_ids(5, -1001, 700, 300)
    assert len(check) == 700 and check != gen.prompt_ids(5, -1002, 700, 300)
    with pytest.raises(ValueError):
        gen.Schedule({**small, "documents": {"lengths": [5000], "each": 1}},
                     5, 5.0, 300)


# ------------------------------------------------------- loads by its name --
def test_cell_reference_and_metrics_load_by_name():
    cell = spec.load_cell(roots.REPO, CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "shared_docs"
    assert cell.config["reference"] == "glm_dsa"
    ref = spec.load_module(roots.REPO, "reference", "glm_dsa")
    assert callable(ref.make_forward(cell.config))
    src = (roots.REPO / "cellbench/reference/glm_dsa.py").read_text()
    assert "dynamo_tpu" not in src.replace("dynamo-tpu", "")
    owed = {m["name"] for m in spec.metrics_for(roots.REPO, CELL, "per_layer")}
    assert set(NEW_METRICS) <= owed
    assert {"kernel.decode_attn_roofline", "kernel.prefill_attn_roofline",
            "device.attn_pct", "sched.ahead_dispatch_pct"} <= owed
    for name in NEW_METRICS:
        desc = spec.load_layer_metric(roots.REPO, name)
        spec.load_module(roots.REPO, "readers", desc["reader"])
        if "cost" in desc["args"]:
            cost(desc["args"]["cost"])
        # no other cell reports it
        others = [w["name"] for w in spec.load_benchmark(roots.REPO)["workloads"]
                  if w["name"] != CELL]
        assert all(name not in {m["name"] for m in spec.metrics_for(
            roots.REPO, w, "per_layer")} for w in others)
    for metric, block in cell.config["kernels"].items():
        cost(block["cost"])
        assert spec.load_layer_metric(roots.REPO, metric)["reader"] == "kernel_roofline"


def test_the_cache_the_file_asks_for_is_the_stated_size():
    cfg = spec.load_cell(roots.REPO, CELL).config
    serve = cfg["serve"]
    tokens = serve["num_blocks"] * serve["block_size"]
    assert tokens >= 450_000 and serve["max_model_len"] >= 32768 + 256 + 384
    docs = sum(spec.load_cell(roots.REPO, CELL).traffic["documents"]["lengths"]) * 4
    assert docs == 294_912 and tokens > docs + 32 * 1000


def test_scope_readers_return_nothing_without_a_profile(tmp_path):
    ctx = {"root": roots.REPO, "trace_dir": None, "trace_interval": None,
           "peaks": None, "records": [], "config": {}, "chips": 1}
    for name in ("scope_roofline", "scope_share"):
        reader = spec.load_module(roots.REPO, "readers", name)
        assert reader.read(ctx, {"scope": "indexer", "cost": "dsa_indexer"}) is None
    assert "no profile" in spec.load_module(
        roots.REPO, "readers", "scope_roofline").missing(
            ctx, {"scope": "indexer", "cost": "dsa_indexer"})


# ---------------------------------------------------------- tiny rehearsal --
@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy glm_moe_dsa decoder under the shared-documents generator (one
    8,192-token document length, 8-16-token questions) in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("glm"))
    shutil.copy(roots.HERE / "data" / "tiny-glm.json", root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-docs.json", root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-glm", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-glm.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-glm.docs", "config": "tiny-glm", "traffic": "tiny-docs",
        "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-glm.docs"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(roots.REPO)}
    keep = tmp_path_factory.mktemp("records")
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, json, shutil, cellbench.run as r\n"
         "orig = r.load_phase\n"
         "async def keep(served, *a, **k):\n"
         "    out = await orig(served, *a, **k)\n"
         "    json.dump({'records': out['records'], 'window': out['window'],\n"
         "               'edges': out['edges']}, open(sys.argv[1], 'w'))\n"
         "    return out\n"
         "r.load_phase = keep\n"
         "sys.exit(r.main(sys.argv[2:]))\n",
         str(keep / "phase.json"), "--workload", "tiny-glm.docs", "--seed",
         str(2**31 + 11), "--seconds", "4", "--trace", "1", "--root",
         str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=600)
    return root, p, keep / "phase.json"


def test_tiny_glm_cell_rehearses(rehearsed):
    _, p, _ = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert m["kv.prefix_hit_pct"]["value"] >= 95
    assert 0 < m["attn.selected_pct"]["value"] < 2      # 64 of ~8,200
    assert m["sched.ahead_dispatch_pct"]["value"] >= 90
    assert m["kv.cut_short_pct"]["value"] == 0
    # off the chip: no peaks, and the CPU's profile names no scope
    assert "kernel.indexer_roofline" not in m and "device.indexer_pct" not in m


def test_prefill_cost_module_counts_what_the_engine_computed(rehearsed):
    """Σ over the window of the cost module's computed tokens = Δ of the
    engine's own counter, to the requests cut by the window's edges."""
    root, p, kept = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    phase = json.loads(kept.read_text())
    (w0, w1), (before, after) = phase["window"], phase["edges"]
    mod = spec.load_module(root, "costs", "mla_sparse_prefill")
    cfg = json.loads((root / "cellbench/configs/tiny-glm.json").read_text())
    counted = sum(take for take, _ in mod.calls(phase["records"], (w0, w1), cfg))
    engine = (after["core.prompt_tokens_computed"]
              - before["core.prompt_tokens_computed"])
    assert engine > 0 and abs(counted - engine) <= 3 * 16
