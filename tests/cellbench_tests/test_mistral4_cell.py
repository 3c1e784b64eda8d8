"""What the Mistral-Small-4 cell adds to the benchmark, as new files alone: a
configuration, a reference, two cost modules, three per-layer metrics and a
cell — and a tiny rehearsal of generator + model + reference end to end in a
copied root.  Nothing here depends on how fast the machine is."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import roots
from cellbench import server, spec

CELL = "mistral-small-4-ep8.docs-shared-closed"
NEW_METRICS = ("attn.ctx_rows_per_decode_row", "moe.held_pick_pct",
               "moe.rows_per_expert")
# the published language model (config.json of the source), by hand
PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 32, "kv_lora_rank": 256,
    "q_lora_rank": 1024, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "moe_intermediate_size": 2048, "intermediate_size": 12288,
    "num_experts_per_tok": 4, "n_shared_experts": 1, "first_k_dense_replace": 0,
    "num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}


def cost(name):
    return spec.load_module(roots.REPO, "costs", name)


def test_files_load_by_name_and_state_the_cut():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["generator"] == "shared_docs"
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    cut = {"num_hidden_layers": 9, "n_routed_experts": 16, "vocab_size": 16384}
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
    assert cfg["expert_parallel"] == {"chips": 8, "router_experts": 128,
                                      "first_expert": 0}
    assert cfg["vocab_parallel"] == {"slices": 8, "slice": 0}
    assert "4 pipeline stages" in cfg["deployment"] and "8 chips" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 6
    assert hasattr(spec.load_module(roots.REPO, "reference", cfg["reference"]),
                   "make_forward")
    for metric, entry in cfg["kernels"].items():
        assert hasattr(cost(entry["cost"]), "cost"), metric
    # the kernels' names are what the patterns match
    from dynamo_tpu.ops.pallas import mla_dense_attention as dense
    src = open(dense.__file__).read()
    for entry in cfg["kernels"].values():
        assert f'name="{entry["pattern"].lstrip("^")}"' in src
    for name in NEW_METRICS:
        listed = [m for m in spec.metrics_for(roots.REPO, CELL, "per_layer")
                  if m["name"] == name]
        assert listed and listed[0]["workloads"] == [CELL]
        assert spec.load_layer_metric(roots.REPO, name)["reader"] == "counter_ratio"


def test_weights_and_cache_are_the_stated_size():
    """4.242 B parameters = 8.48 GB and 14,400 blocks of 32 tokens x 9 layers
    x 768 B = 3.19 GB, from the program's own shapes: 69% of 16.9 GB."""
    cfg = spec.load_cell(roots.REPO, CELL).config
    model = server.resolve(cfg["model_class"])(server.model_config(cfg))
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    layer = 28_049_408 + 9_472 + 25_165_824 + 524_288 + 16 * 25_165_824
    assert n == 9 * layer + 2 * 16384 * 4096 + 4096
    assert 8.48e9 < 2 * n < 8.49e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"]))
    assert cache["latent"].shape == (9, 14400, 32, 384)
    held = cache["latent"].size * 2
    assert held == 14400 * 32 * 9 * 768 == 3_185_049_600
    assert (2 * n + held) / (15.75 * 2**30) > 0.6
    # the documents and 32 live rows fit: 12 documents + 32 x (256 + 384)
    assert serve["num_blocks"] * 32 >= 4 * (16384 + 24576 + 32768) + 32 * 640


def test_decode_cost_is_the_hand_count():
    """Two rows at contexts 1,000 and 24,000: 25,000 rows read; per layer and
    row 32 heads x 2 x (320 + 256) operations and 320 bf16 elements; per call
    32 x (320 + 256) elements of query and output."""
    cfg = spec.load_cell(roots.REPO, CELL).config
    ops, nbytes = cost("mla_dense_decode").cost(cfg, [1000, 24000])
    assert ops == 9 * 2 * 32 * (320 + 256) * 25000
    assert nbytes == 9 * 2 * (320 * 25000 + 32 * (320 + 256) * 2)
    assert ops / nbytes == pytest.approx(57.6, rel=0.01)   # near both limits
    records = [{"prompt_len": 16384 + 100, "token_times": [0.5, 1.5, 2.5]}]
    assert cost("mla_dense_decode").calls(records, (1.0, 3.0), cfg) == [
        16485, 16486]


def test_prefill_cost_counts_the_question_over_document_and_question():
    mod = cost("mla_dense_prefill")
    cfg = spec.load_cell(roots.REPO, CELL).config
    assert mod.rows(3, 0) == 1 + 2 + 3
    assert mod.rows(4, 10) == 11 + 12 + 13 + 14
    assert mod.computed(24576 + 130) == (130, 24576)
    assert mod.chunks(5000, 2048) == [(2048, 0), (2048, 2048), (904, 4096)]
    records = [{"prompt_len": 24576 + 130, "first": 1.5},
               {"prompt_len": 16384 + 64, "first": 9.0}]
    calls = mod.calls(records, (1.0, 2.0), cfg)
    assert calls == [(130, 24576)]
    ops, nbytes = mod.cost(cfg, calls)
    rows = 130 * 24576 + 130 * 131 // 2
    assert ops == 9 * 2 * 32 * 576 * rows
    assert nbytes == 9 * 2 * (320 * (24576 + 130) + 32 * 576 * 130)


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy mistral4 decoder under the shared-documents generator (two
    8,192-token documents, 8-16-token questions) in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("mistral4"))
    shutil.copy(roots.HERE / "data" / "tiny-mistral4.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-docs-two.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-mistral4", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-mistral4.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-mistral4.docs", "config": "tiny-mistral4",
        "traffic": "tiny-docs-two", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-mistral4.docs"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(roots.REPO)}
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "tiny-mistral4.docs", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", "1", "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)


def test_tiny_cell_rehearses_and_reports_the_three_new_metrics(rehearsed):
    p = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["attempted"] > 0
    m = out["metrics"]
    # every decode row read its document and its question, whole
    assert 8192 < m["attn.ctx_rows_per_decode_row"]["value"] < 8192 + 32
    # 2 of the router's 8 experts are held: a quarter of the picks, roughly
    assert 10 < m["moe.held_pick_pct"]["value"] < 40
    assert m["moe.rows_per_expert"]["value"] > 0
    assert m["kv.cut_short_pct"]["value"] == 0
