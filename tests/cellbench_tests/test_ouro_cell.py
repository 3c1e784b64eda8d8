"""What the Ouro-2.6B cell adds to the benchmark, as new files alone: a
configuration of a looped decoder, its plain reference, a traffic mix for
the generator that is there, one per-layer metric on the reader that is
there — and a tiny rehearsal of a looped model end to end in a copied root."""

import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import roots
from cellbench import spec

CELL = "ouro-2.6b.reason-closed"
METRIC = "loop.passes_per_token"
CATALOG = {   # the published keys of the catalog's row, none cut
    "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
    "num_attention_heads": 16, "num_hidden_layers": 48,
    "num_key_value_heads": 16, "vocab_size": 49152, "total_ut_steps": 4,
    "early_exit_threshold": 1, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "tie_word_embeddings": False, "hidden_act": "silu", "model_type": "ouro"}


# ----------------------------------------------------------- configuration --
def test_configuration_is_the_published_one_uncut():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cfg["reduced"] == []
    for key, value in CATALOG.items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert cfg["architectures"] == ["OuroForCausalLM"]
    assert any("architectures" in line for line in cfg["assumed"])
    entry = next(c for c in spec.load_benchmark(roots.REPO)["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_attention_layers_are_the_layer_applications():
    """The attention rooflines count ``attention_layers`` kernel calls a
    program: every pass of every layer attends, on a cache of its own."""
    cfg = spec.load_cell(roots.REPO, CELL).config
    assert cfg["attention_layers"] == (
        cfg["num_hidden_layers"] * cfg["total_ut_steps"]) == 192
    for name in ("decode_attention", "prefill_attention"):
        mod = spec.load_module(roots.REPO, "costs", name)
        calls = [200, 300] if name == "decode_attention" else mod.calls(
            [{"prompt_len": 100, "first": 1.5}], (1.0, 2.0), cfg)
        ops, nbytes = mod.cost(cfg, calls)
        once = mod.cost({**cfg, "attention_layers": 48}, calls)
        assert ops == 4 * once[0] and nbytes == 4 * once[1]


def test_the_cache_the_file_asks_for_is_the_stated_size():
    """1.5 MiB a token over 192 cache layers; the traffic's worst case (16
    clients x (128 + 256) tokens) has to fit, because admission reserves
    nothing for an answer."""
    from dynamo_tpu.models.llama import LlamaModel

    from cellbench import server

    cell = spec.load_cell(roots.REPO, CELL)
    cfg, serve, traffic = cell.config, cell.config["serve"], cell.traffic
    model = LlamaModel(server.model_config(cfg))
    assert model.cache_layers == 192 and model.config.ut_steps == 4
    assert model.config.post_norms and not model.config.qk_norm
    import jax

    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"]))
    assert cache.shape == (192, serve["num_blocks"], 2, 32, 2048)
    per_token = cache.size * cache.dtype.itemsize // (
        serve["num_blocks"] * serve["block_size"])
    assert per_token == 2 * 16 * 128 * 2 * 192 == 1_572_864
    worst = traffic["clients"] * (
        traffic["prompt_len"]["max"] + traffic["output_len"]["max"])
    assert traffic["clients"] == serve["max_batch_size"] == 16
    assert worst == 6144 and serve["num_blocks"] * 32 >= worst
    assert serve["num_blocks"] >= 192
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= serve["max_model_len"]
    # the weights: 2.668 B parameters, 4.97 GiB in bf16
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 2.66e9 < n < 2.68e9
    assert set(shapes) >= {"exit_gate_w", "exit_gate_b", "lm_head"}


def test_traffic_is_the_issues():
    t = spec.load_cell(roots.REPO, CELL).traffic
    assert t["generator"] == "mix" and t["loop"] == "closed"
    assert t["prompt_len"] == {"dist": "uniform", "min": 64, "max": 128}
    assert t["output_len"] == {"dist": "uniform", "min": 128, "max": 256}
    assert t["sampling"] == {"temperature": 0.7, "top_p": 0.9}
    assert t["ramp_s"] == 10 and t["population_seed"] == 0


def test_cell_reference_and_metric_load_by_name():
    cell = spec.load_cell(roots.REPO, CELL)
    assert cell.config["reference"] == "ouro_loop"
    ref = spec.load_module(roots.REPO, "reference", "ouro_loop")
    assert callable(ref.make_forward(cell.config))
    src = (roots.REPO / "cellbench/reference/ouro_loop.py").read_text()
    assert "dynamo_tpu" not in src.replace("dynamo-tpu", "")
    owed = {m["name"] for m in spec.metrics_for(roots.REPO, CELL, "per_layer")}
    assert METRIC in owed
    assert {"kernel.decode_attn_roofline", "kernel.prefill_attn_roofline",
            "device.head_pct", "sched.ahead_dispatch_pct",
            "kv.cut_short_pct"} <= owed
    desc = spec.load_layer_metric(roots.REPO, METRIC)
    assert desc["reader"] == "counter_ratio" and desc["layer"] == "engine step"
    others = [w["name"] for w in spec.load_benchmark(roots.REPO)["workloads"]
              if w["name"] != CELL]
    assert all(METRIC not in {m["name"] for m in spec.metrics_for(
        roots.REPO, w, "per_layer")} for w in others)


def test_passes_per_token_reads_the_two_counters():
    reader = spec.load_module(roots.REPO, "readers", "counter_ratio")
    args = spec.load_layer_metric(roots.REPO, METRIC)["args"]
    edges = ({"core.loop_passes_total": 400, "core.loop_tokens_total": 100},
             {"core.loop_passes_total": 4400, "core.loop_tokens_total": 1100})
    assert reader.read({"edges": edges}, args) == 4.0
    # a program without the counters (the parent's): nothing, and no raise
    assert reader.read({"edges": ({}, {})}, args) is None


# --------------------------------------------------------------- reference --
def test_reference_selects_by_the_cumulated_exit_probability():
    ref = spec.load_module(roots.REPO, "reference", "ouro_loop")
    gates = jnp.asarray([[3.0, -3.0, -3.0, 0.0],     # pass 0
                         [0.0, 3.0, -3.0, 0.0],      # pass 1
                         [0.0, 0.0, -3.0, 3.0]])     # pass 2 (the last)
    # token 0 exits at once; token 1 at pass 1 (0.047 + 0.953 * 0.953);
    # token 2 never reaches 0.5 before the last; token 3: 0.5 at pass 0
    assert ref.exit_pass(gates, 0.5).tolist() == [0, 1, 2, 0]
    assert ref.exit_pass(gates, 1.0).tolist() == [2, 2, 2, 2]


# ---------------------------------------------------------- tiny rehearsal --
@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy looped decoder (3 passes over 2 layers) under a closed loop of
    sampled answers, in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("ouro"))
    shutil.copy(roots.HERE / "data" / "tiny-ouro.json", root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason.json", root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-ouro", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-ouro.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-ouro.reason", "config": "tiny-ouro",
        "traffic": "tiny-reason", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-ouro.reason"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(roots.REPO)}
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "tiny-ouro.reason", "--seed", str(2**31 + 39), "--seconds", "4",
         "--trace", "1", "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=600)


def test_tiny_looped_cell_rehearses_and_owes_passes_per_token(rehearsed):
    p = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert m[METRIC]["value"] == 3.0          # three passes a token, all run
    assert m["sched.ahead_dispatch_pct"]["value"] >= 90
    assert m["kv.cut_short_pct"]["value"] == 0
    assert not any(l.startswith("# not reported: " + METRIC) for l in lines)
