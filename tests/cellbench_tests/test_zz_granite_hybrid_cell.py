"""What the granite-4.0-h-small cell adds to the benchmark, as new files
alone: a configuration, a reference, a cost module, two per-layer metrics and
a cell on the traffic mix that was there — and a tiny rehearsal of generator +
model + reference end to end in a copied root.  Nothing here depends on how
fast the machine is.  (Named to sort last: ROADMAP R1 (11).)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "granite-4.0-h-small-ep2.reason-long-closed"
SOLAR = "solar-open2-ep16.reason-long-closed"
NEW_METRICS = {"kernel.ssm_state_roofline": ("scope_roofline", "ssm_state"),
               "device.ssm_pct": ("scope_share", "ssm")}


def test_files_load_by_name_and_the_traffic_is_the_other_recurrent_cell_s():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    assert traffic == spec.load_cell(roots.REPO, SOLAR).traffic
    assert traffic["clients"] == cfg["serve"]["max_batch_size"] == 64
    # the check's 700-token prompt crosses a dispatch (512) and, inside the
    # first, an SSD chunk (256)
    assert cfg["mamba_chunk_size"] == 256
    assert cfg["serve"]["prefill_chunk_tokens"] == 512 < 700
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 10
    assert cfg["attention_layers"] == kinds.count("attention") == 1
    assert kinds.index("attention") == 5
    assert len(cfg["assumed"]) >= 6 and "8 chips" in cfg["deployment"]
    ref = spec.load_module(roots.REPO, "reference", cfg["reference"])
    assert hasattr(ref, "make_forward") and hasattr(ref, "make_layer")
    text = (roots.REPO / "cellbench/reference/granite_hybrid.py").read_text()
    assert "dynamo_tpu" not in text.replace("dynamo-tpu", "")
    assert "kernels" not in cfg        # the GQA layer uses the default kernels
    for name, (reader, scope) in NEW_METRICS.items():
        listed = [m for m in spec.metrics_for(roots.REPO, CELL, "per_layer")
                  if m["name"] == name]
        assert listed and listed[0]["workloads"] == [CELL]
        metric = spec.load_layer_metric(roots.REPO, name)
        assert metric["reader"] == reader and metric["args"]["scope"] == scope
        assert metric["moves"] == "itl_p95_ms"
    assert "two program classes" in spec.load_layer_metric(
        roots.REPO, "kernel.ssm_state_roofline")["note"]
    others = [w["name"] for w in spec.load_benchmark(roots.REPO)["workloads"]
              if w["name"] != CELL]
    assert all(name not in {m["name"] for m in spec.metrics_for(
        roots.REPO, w, "per_layer")} for w in others for name in NEW_METRICS)
    # ... and the delta rule's two are not owed here
    mine = {m["name"] for m in spec.metrics_for(roots.REPO, CELL, "per_layer")}
    assert not {"kernel.linear_attn_roofline", "device.linear_attn_pct"} & mine


def test_the_file_is_the_published_model_cut_as_stated():
    """Every number of the catalog's row under its own key but the three
    reduced (and the cut ``layer_types``); the parameter count of the cut
    from the program's own shapes (4.757 B = 9.51 GB); the state and the pool
    the ``serve`` block asks for."""
    import jax
    import jax.numpy as jnp

    from cellbench import server

    cfg = spec.load_cell(roots.REPO, CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_local_experts", "vocab_size"]
    published = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 10,
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "rope_scaling": None, "hidden_act": "silu",
        "normalization_function": "rmsnorm", "model_type": "granitemoehybrid"}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (10, 36, 50176)
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["expert_parallel"] == {"chips": 2, "router_experts": 72,
                                      "first_expert": 0}
    assert cfg["vocab_parallel"] == {"slices": 2, "slice": 0}
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    mc = server.model_config(cfg)
    assert (mc.recurrence, mc.state_shape, mc.conv_width) == (
        "ssd", (128, 64, 128), 8448)
    assert mc.gqa_layers == (5,) and not mc.gqa_gate
    model = server.resolve(cfg["model_class"])(mc)
    assert model.sm_scale == 0.0078125
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    assert "lm_head" not in shapes
    n = sum(a.size for a in jax.tree.leaves(shapes))
    expert = 3 * 4096 * 768
    outside = 3 * 4096 * 1536 + 4096 * 72 + 4096     # shared, router, norm
    mamba = (4096 * (8192 + 8448 + 128) + 8192 * 4096 + 8448 * 4 + 8448
             + 3 * 128 + 8192 + 4096)
    gqa = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 4096
    assert n == (9 * mamba + gqa + 10 * (outside + 36 * expert)
                 + 50176 * 4096 + 4096)
    assert 9.50e9 < 2 * n < 9.53e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"], slots=serve["max_batch_size"]))
    assert cache["kv"].shape == (1, serve["num_blocks"], 2, 32, 1024)
    assert cache["state"].shape == (9, 64, 128, 64, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (9, 64, 3, 8448)
    assert model.state_bytes_per_slot() == 9 * (4 * 2**20 + 3 * 8448 * 2)
    assert serve["num_blocks"] * 32 >= 64 * 3072     # the traffic's worst case
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert 0.74 < (2 * n + held) / 16.9e9 < 0.78


def test_state_cost_is_the_hand_count():
    """A decode row: 9 layers x (2 x 128 x 64 x 128 x 4 B of state + x, y of
    8,192, B and C of 128 and 128 steps in float32), 5 operations a state
    element.  A 512-token chunk: the state once, its rows, two pieces of
    256."""
    cost = spec.load_module(roots.REPO, "costs", "ssm_state")
    cfg = spec.load_cell(roots.REPO, CELL).config
    state = 128 * 64 * 128
    row = 2 * 8192 + 2 * 128 + 128
    ops, nbytes = cost.cost(cfg, [("d",)])
    assert ops == 9 * 5 * state
    assert nbytes == 9 * (2 * state * 4 + 4 * row)
    # 64 rows: 4.8 GB a step, the issue's count
    assert 4.8e9 < cost.cost(cfg, [("d",)] * 64)[1] < 4.9e9
    piece = lambda q: 2 * q * q * 128 + 128 * (2 * q * q * 64 + 4 * q * 128 * 64)
    ops, nbytes = cost.cost(cfg, [("p", 512)])
    assert ops == 9 * 2 * piece(256)
    assert nbytes == 9 * (2 * state * 4 + 4 * row * 512)
    assert cost.cost(cfg, [("p", 300)])[0] == 9 * (piece(256) + piece(44))
    records = [{"prompt_len": 1300, "first": 1.5, "token_times": [1.5, 1.6, 2.5]},
               {"prompt_len": 600, "first": 9.0, "token_times": [9.0, 9.1]}]
    assert sorted(cost.calls(records, (1.0, 2.0), cfg)) == [
        ("d",), ("p", 276), ("p", 512), ("p", 512)]
    # a share of 100 would be the least time: by bytes for a decode row, by
    # operations for neither (the chunk's are 6 GFLOP a dispatch)
    ops, nbytes = cost.cost(cfg, [("d",)] * 64 + [("p", 512)])
    assert nbytes / 819e9 > ops / 197e12


def test_the_scopes_the_metrics_read_are_the_model_s():
    """``scope_roofline`` matches one scope name at any depth: ``ssm`` round
    the whole mixer inside ``attn``, ``ssm_state`` inside it round the
    state's read-update-write; the projections are ``attn_proj`` /
    ``attn_out`` as the delta rule's are."""
    import dynamo_tpu.models.hybrid_linear as hybrid

    src = open(hybrid.__file__).read()
    assert 'jax.named_scope("attn"), jax.named_scope("ssm")' in src
    assert src.count('jax.named_scope("ssm_state")') == 1
    assert src.index('named_scope("ssm")') < src.index('named_scope("ssm_state")')
    body = src[src.index("def _ssd"):src.index("def _linear")]
    assert body.count('named_scope("attn_proj")') == 1
    assert body.count('named_scope("attn_out")') == 1


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy granitemoehybrid decoder (m m A m m m, prefill chunk 64 = two
    SSD pieces of 32) under the fixed-order generator with prompts of 40-150
    tokens in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("granite"))
    shutil.copy(roots.HERE / "data" / "tiny-granite-hybrid.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason-long.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-granite-hybrid", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-granite-hybrid.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-granite-hybrid.reason", "config": "tiny-granite-hybrid",
        "traffic": "tiny-reason-long", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-granite-hybrid.reason"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(roots.REPO)}
    keep = tmp_path_factory.mktemp("records")
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, json, cellbench.run as r\n"
         "orig = r.load_phase\n"
         "async def keep(served, *a, **k):\n"
         "    out = await orig(served, *a, **k)\n"
         "    json.dump({'edges': out['edges']}, open(sys.argv[1], 'w'))\n"
         "    return out\n"
         "r.load_phase = keep\n"
         "sys.exit(r.main(sys.argv[2:]))\n",
         str(keep / "phase.json"), "--workload", "tiny-granite-hybrid.reason",
         "--seed", str(2**31 + 31), "--seconds", "3", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, keep / "phase.json"


def test_tiny_cell_rehearses_and_counts_what_its_state_layers_did(rehearsed):
    """Nothing is asserted of any time.  Off the chip the two new metrics
    are left out (no peaks, and the CPU's profile names no scope) and
    everything the cell owes besides is reported; the counters are the ones
    the delta rule's cell reads."""
    p, kept = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert not set(NEW_METRICS) & set(m)
    assert m["kv.cut_short_pct"]["value"] == 0
    for owed in ("engine.ttft_ms", "engine.turn_wait_ms", "engine.prefill_ms",
                 "http.queue_wait_ms", "sched.decode_rows_per_dispatch",
                 "sched.ahead_dispatch_pct"):
        assert owed in m, owed
    before, after = json.loads(kept.read_text())["edges"]
    core = lambda edge, key: edge["core." + key]
    assert core(after, "state_position_mismatches_total") == 0
    assert core(after, "prefix_reuse") == 0
    assert core(after, "state_update_kernel") == 0
    assert core(after, "state_layers") == 5 and core(after, "cache_layers") == 1
    assert core(after, "state_bytes_per_slot") == 5 * (
        4 * 32 * 16 * 4 + 3 * 160 * 4)
    resets = (core(after, "state_resets_total")
              - core(before, "state_resets_total"))
    assert abs(resets - out["attempted"]) <= 4 < resets
    tokens = (core(after, "state_tokens_total")
              - core(before, "state_tokens_total"))
    assert tokens % 5 == 0 and tokens >= 5 * 40 * out["attempted"]
    touched = (core(after, "moe_experts_touched_total")
               - core(before, "moe_experts_touched_total"))
    assert touched > 0
