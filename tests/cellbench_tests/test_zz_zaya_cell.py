"""What the ZAYA1-8B cell adds to the benchmark, as new files alone: a
configuration, a reference, three per-layer metrics read by readers that were
there and a cell on the traffic mix that was there — and a tiny rehearsal of
generator + model + reference end to end in a copied root.  Nothing here
depends on how fast the machine is.  (Named to sort last: ROADMAP R1 (11).)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "zaya1-8b.reason-long-closed"
GRANITE = "granite-4.0-h-small-ep2.reason-long-closed"
NEW_METRICS = {
    "device.cca_mix_pct": ("scope_share", {"scope": "cca"}, "itl_p95_ms"),
    "device.router_pct": ("scope_share", {"scope": "router"}, "itl_p95_ms"),
    "moe.skip_pick_pct": ("counter_ratio", {
        "num": "core.moe_skip_picks_total",
        "den": "core.moe_router_picks_total", "scale": 100.0}, "tok_s_chip")}


def test_files_load_by_name_and_the_traffic_is_the_recurrent_cells_unchanged():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and cell.params == {}
    assert traffic == spec.load_cell(roots.REPO, GRANITE).traffic
    assert traffic["clients"] == cfg["serve"]["max_batch_size"] == 64
    # the check's 700-token prompt crosses a chunk: the tails' carry is
    # inside ``correct``
    assert cfg["serve"]["prefill_chunk_tokens"] == 512 < 700
    assert "attention_layers" not in cfg      # every layer attends
    assert len(cfg["assumed"]) >= 8 and "2 pipeline stages" in cfg["deployment"]
    ref = spec.load_module(roots.REPO, "reference", cfg["reference"])
    assert hasattr(ref, "make_forward")
    text = (roots.REPO / "cellbench/reference/zaya_cca.py").read_text()
    assert "dynamo_tpu" not in text.replace("dynamo-tpu", "")
    assert 'default_matmul_precision("highest")' in text
    listed = spec.metrics_for(roots.REPO, CELL, "per_layer")
    bench = spec.load_benchmark(roots.REPO)
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_METRICS)
    for name, (reader, args, moves) in NEW_METRICS.items():
        mine = [m for m in listed if m["name"] == name]
        assert mine and mine[0]["workloads"] == [CELL]
        metric = spec.load_layer_metric(roots.REPO, name)
        assert (metric["reader"], metric["args"]) == (reader, args)
        assert metric["moves"] == mine[0]["moves"] == moves
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    assert len(others) == 9 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(name not in {m["name"] for m in spec.metrics_for(
        roots.REPO, w, "per_layer")} for w in others for name in NEW_METRICS)
    # ... and the recurrences' four are not owed here; both attention
    # rooflines are, from the published head counts
    names = {m["name"] for m in listed}
    assert not {"kernel.linear_attn_roofline", "device.linear_attn_pct",
                "kernel.ssm_state_roofline", "device.ssm_pct"} & names
    assert {"kernel.decode_attn_roofline", "kernel.prefill_attn_roofline"} <= names
    cost = spec.load_module(roots.REPO, "costs", "decode_attention")
    ops, nbytes = cost.cost(cfg, [2000])
    assert ops == 20 * 4 * 8 * 128 * 2000
    assert nbytes == 20 * 2 * (2 * 2 * 128 * 2000 + 2 * 8 * 128)


def test_the_file_is_the_published_model_cut_in_depth_alone():
    """Every key of the catalog's row at its published value but the depth
    (and ``layer_types`` cut with it); the parameter count from the program's
    own shapes (20 x 207.6 M + 537 M = 4.69 B = 9.38 GB); the tails and the
    pool the ``serve`` block asks for."""
    import jax
    import jax.numpy as jnp

    from cellbench import server

    cfg = spec.load_cell(roots.REPO, CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "max_position_embeddings": 131072,
        "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16, "num_experts_per_tok": 1,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 262272,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"}}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 20
    assert cfg["layer_types"] == ["hybrid"] * cfg["num_hidden_layers"]
    assert cfg["pipeline_parallel"] == {"stages": 2, "stage": 0,
                                        "layers_a_stage": 20}
    mc = server.model_config(cfg)
    assert (mc.conv_width, mc.tail_width, mc.rotary_dim, mc.rope_theta) == (
        1280, 2 * 1280 + 128, 64, 5e6)
    assert (mc.n_routed_experts, mc.router_outputs) == (16, 17)
    model = server.resolve(cfg["model_class"])(mc)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    projections = 2 * 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128
    convolutions = 1280 * 2 + 1280 + 2 * 10 * 128 * 128 + 1280 + 2
    router = (2048 * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256)
              + 256 * 17 + 17)
    experts = 16 * 3 * 2048 * 2048
    layer = (projections + convolutions + router + experts
             + 2 * 2048 + 2 * 4 * 2048)             # two norms, two merges
    assert layer == 207_583_763
    assert n == 20 * layer + 262272 * 2048 + 2048 == 4_688_810_364
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    assert 9.37e9 < nbytes(shapes) < 9.39e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"], slots=serve["max_batch_size"]))
    assert cache["kv"].shape == (20, serve["num_blocks"], 2, 32, 256)
    assert cache["state"].shape == (20, 64, 2688)
    assert cache["state"].dtype == jnp.bfloat16
    assert model.state_bytes_per_slot() == 20 * 2688 * 2
    assert cache["moe_counts"].shape == (20, 1, len(model.moe_count_keys)) == (20, 1, 8)
    assert serve["num_blocks"] * 32 >= 64 * 3072     # the traffic's worst case
    assert nbytes(cache["kv"]) == 6272 * 32 * 20 * 1024       # 20 KiB a token
    assert 0.79 < (nbytes(shapes) + nbytes(cache)) / 16.9e9 < 0.81


def test_the_scopes_the_metrics_read_are_the_model_s():
    """``scope_share`` matches one scope name at any depth: ``cca`` inside
    ``attn`` round everything between the projections and the paged
    attention, ``router`` inside ``mlp`` round the router and its counts."""
    import dynamo_tpu.models.zaya as zaya

    src = open(zaya.__file__).read()
    body = src[src.index("def _cca"):src.index("def _experts")]
    for scope in ("attn_proj", "attn", "cca", "attn_out"):
        assert body.count(f'jax.named_scope("{scope}")') == 1, scope
    assert body.index('named_scope("attn")') < body.index('named_scope("cca")')
    assert body.index('named_scope("cca")') < body.index("paged_gqa(")
    rest = src[src.index("def _experts"):]
    assert rest.count('jax.named_scope("router")') == 1
    assert rest.index('named_scope("router")') < rest.index('named_scope("moe_experts")')
    assert rest.count('jax.named_scope("mlp")') == 1


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy zaya decoder (4 layers, 8 / 2 heads of 16, four experts and the
    skip, prefill chunk 64) under the fixed-order generator with prompts of
    40-150 tokens in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("zaya"))
    shutil.copy(roots.HERE / "data" / "tiny-zaya.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason-long.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-zaya", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-zaya.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-zaya.reason", "config": "tiny-zaya",
        "traffic": "tiny-reason-long", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-zaya.reason"]
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
         str(keep / "phase.json"), "--workload", "tiny-zaya.reason",
         "--seed", str(2**31 + 57), "--seconds", "3", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, keep / "phase.json"


def test_tiny_cell_rehearses_and_counts_its_picks_and_tails(rehearsed):
    """Nothing is asserted of any time.  Off the chip the two scope shares
    are left out (the CPU's profile names no scope); the counter ratio is
    read, and everything the cell owes besides is reported."""
    p, kept = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert not {"device.cca_mix_pct", "device.router_pct"} & set(m)
    assert 0 < m["moe.skip_pick_pct"]["value"] < 60       # 1 of 5 outputs
    assert m["kv.cut_short_pct"]["value"] == 0
    for owed in ("engine.ttft_ms", "engine.turn_wait_ms", "engine.prefill_ms",
                 "http.queue_wait_ms", "sched.decode_rows_per_dispatch",
                 "sched.ahead_dispatch_pct"):
        assert owed in m, owed
    before, after = json.loads(kept.read_text())["edges"]
    core = lambda edge, key: edge["core." + key]
    moved = lambda key: core(after, key) - core(before, key)
    assert core(after, "state_position_mismatches_total") == 0
    assert core(after, "prefix_reuse") == 0
    assert core(after, "state_update_kernel") == 0
    assert core(after, "state_layers") == core(after, "cache_layers") == 4
    assert core(after, "state_bytes_per_slot") == 4 * (2 * 160 + 16) * 4
    assert abs(moved("state_resets_total") - out["attempted"]) <= 4
    assert moved("moe_router_picks_total") == moved("state_tokens_total") > 0
    assert (moved("moe_held_picks_total") + moved("moe_skip_picks_total")
            == moved("moe_router_picks_total"))
    assert 0 < moved("moe_experts_touched_total") <= 4 * moved(
        "moe_expert_layer_calls_total")
