"""What the AI21-Jamba2-3B cell adds to the benchmark, as new files alone: a
configuration, a reference, two cost modules, three per-layer metrics and a
cell on the traffic mix that was there — and a tiny rehearsal of generator +
model + reference end to end in a copied root.  Every entry is found by its
name, none by its place in a list.  Nothing here depends on how fast the
machine is.  (Named to sort last: ROADMAP R1 (11).)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "jamba2-3b.reason-long-closed"
GRANITE = "granite-4.0-h-small-ep2.reason-long-closed"
NEW_METRICS = {
    "device.selective_pct": ("scope_share", "selective", "itl_p95_ms"),
    "kernel.selective_step_roofline": (
        "scope_roofline", "selective_step", "itl_p95_ms"),
    "kernel.selective_scan_roofline": (
        "scope_roofline", "selective_scan", "ttft_mean_ms")}
SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"


def test_files_load_by_name_and_the_traffic_is_the_recurrent_cells():
    bench = spec.load_benchmark(roots.REPO)
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["source"] == SOURCE and entry["reduced"] == []
    cell = spec.load_cell(roots.REPO, CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    assert traffic == spec.load_cell(roots.REPO, GRANITE).traffic
    assert traffic["clients"] == cfg["serve"]["max_batch_size"] == 64
    # the check's 700-token prompt crosses a dispatch (512)
    assert cfg["serve"]["prefill_chunk_tokens"] == 512 < 700
    attending = [i for i in range(cfg["num_hidden_layers"])
                 if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]]
    assert attending == [7, 21]
    assert cfg["attention_layers"] == len(attending) == 2
    assert len(cfg["assumed"]) >= 5 and "one v5e chip" in cfg["deployment"]
    assert "no other chip" in cfg["deployment"]
    ref = spec.load_module(roots.REPO, "reference", cfg["reference"])
    assert hasattr(ref, "make_forward")
    text = (roots.REPO / "cellbench/reference/jamba_hybrid.py").read_text()
    assert "dynamo_tpu" not in text.replace("dynamo-tpu", "")
    assert "kernels" not in cfg        # the MQA layers use the default kernels
    for name, (reader, scope, moves) in NEW_METRICS.items():
        listed = [m for m in spec.metrics_for(roots.REPO, CELL, "per_layer")
                  if m["name"] == name]
        assert listed and listed[0]["workloads"] == [CELL]
        metric = spec.load_layer_metric(roots.REPO, name)
        assert metric["reader"] == reader and metric["args"]["scope"] == scope
        assert metric["moves"] == listed[0]["moves"] == moves
    for name in ("kernel.selective_step_roofline",
                 "kernel.selective_scan_roofline"):
        assert "one program class" in spec.load_layer_metric(
            roots.REPO, name)["note"]
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    assert all(name not in {m["name"] for m in spec.metrics_for(
        roots.REPO, w, "per_layer")} for w in others for name in NEW_METRICS)
    # ... and the other recurrences' are not owed here
    mine = {m["name"] for m in spec.metrics_for(roots.REPO, CELL, "per_layer")}
    assert not {"kernel.linear_attn_roofline", "device.linear_attn_pct",
                "kernel.ssm_state_roofline", "device.ssm_pct"} & mine
    assert {"kernel.decode_attn_roofline", "kernel.prefill_attn_roofline",
            "device.attn_pct", "device.mlp_pct"} <= mine
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_file_is_the_published_model_whole():
    """Every number of the catalog's row under its own key and nothing
    reduced; the parameter count from the program's own shapes
    (3,029,337,472); the state and the pool the ``serve`` block asks for."""
    import jax
    import jax.numpy as jnp

    from cellbench import server

    cfg = spec.load_cell(roots.REPO, CELL).config
    assert cfg["reduced"] == [] and cfg["source"] == SOURCE
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
        "num_hidden_layers": 28, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
        "sliding_window": None, "tie_word_embeddings": True,
        "use_mamba_kernels": True, "vocab_size": 65536}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    mc = server.model_config(cfg)
    assert (mc.recurrence, mc.state_shape, mc.conv_width, mc.gate_rank) == (
        "selective", (16, 40, 128), 5120, 160)
    assert mc.gqa_layers == (7, 21) and not mc.gqa_gate
    assert mc.n_routed_experts == 0 and mc.moe_intermediate_size == 8192
    model = server.resolve(cfg["model_class"])(mc)
    assert model.sm_scale == 128 ** -0.5
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    assert "lm_head" not in shapes
    n = sum(a.size for a in jax.tree.leaves(shapes))
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 160 + 16 + 16 + 5120 * 2560)
    assert mamba == 41_241_792
    mlp, gqa = 3 * 2560 * 8192, 2 * 2560 * 2560 + 2 * 2560 * 128
    assert n == (26 * (mamba + mlp + 5120) + 2 * (gqa + mlp + 5120)
                 + 65536 * 2560 + 2560) == 3_029_337_472
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert 6.05e9 < nbytes < 6.07e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"], slots=serve["max_batch_size"]))
    assert cache["kv"].shape == (2, serve["num_blocks"], 2, 32, 128)
    assert cache["state"].shape == (26, 64, 16, 40, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (26, 64, 3, 5120)
    assert model.state_bytes_per_slot() == 26 * (327_680 + 30_720) == 9_318_400
    assert serve["num_blocks"] * 32 >= 64 * 3072     # the traffic's worst case
    # a token's K/V in the two attending layers: 1 KiB
    assert cache["kv"].size * 2 // (serve["num_blocks"] * 32) == 1024
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert 0.40 < (nbytes + held) / 16e9 < 0.45


def test_the_two_costs_are_the_hand_counts():
    """A decode step of 64 rows x 26 layers moves 64 x 26 x (2 x 327,680 +
    2 x 30,720) B = 1.19 GB of what the slots keep; a 512-token chunk moves a
    slot's own once and its rows a token, 81,920 exponentials a token and
    layer.  One module a program class: neither finds the other's calls."""
    step = spec.load_module(roots.REPO, "costs", "selective_step")
    scan = spec.load_module(roots.REPO, "costs", "selective_scan")
    cfg = spec.load_cell(roots.REPO, CELL).config
    assert step.geometry(cfg) == (26, 16, 5120, 4)
    assert step.slot_bytes(cfg) == 2 * 327_680 + 2 * 30_720
    assert 64 * 26 * step.slot_bytes(cfg) == 1_192_755_200
    row = 3 * 5120 + 2 * 16
    ops, nbytes = step.cost(cfg, [("d",)])
    assert ops == 26 * 6 * 81_920
    assert nbytes == 26 * (716_800 + 4 * row)
    assert 1.19e9 < step.cost(cfg, [("d",)] * 64)[1] < 1.31e9
    ops, nbytes = scan.cost(cfg, [("p", 512), ("p", 300)])
    assert ops == 26 * 6 * 81_920 * 812
    assert nbytes == 26 * (2 * 716_800 + 4 * row * 812)
    # ~32 MB a layer and 512-token chunk: ~40 us at 819 GB/s
    assert 31e6 < scan.cost(cfg, [("p", 512)])[1] / 26 < 33e6
    records = [{"prompt_len": 1300, "first": 1.5, "token_times": [1.5, 1.6, 2.5]},
               {"prompt_len": 600, "first": 9.0, "token_times": [9.0, 9.1]}]
    assert step.calls(records, (1.0, 2.0), cfg) == [("d",)]
    assert sorted(scan.calls(records, (1.0, 2.0), cfg)) == [
        ("p", 276), ("p", 512), ("p", 512)]
    # by bytes, both: peaks.json has no peak for the vector unit, and against
    # the matrix unit's these operations are nothing
    for cost, calls in ((step, [("d",)] * 64), (scan, [("p", 512)])):
        ops, nbytes = cost.cost(cfg, calls)
        assert nbytes / 819e9 > 10 * ops / 197e12
    # the attention costs read the two attending layers and the one K/V head
    decode = spec.load_module(roots.REPO, "costs", "decode_attention")
    ops, nbytes = decode.cost(cfg, [1000])
    assert ops == 2 * 4.0 * 20 * 128 * 1000
    assert nbytes == 2 * 2 * (2.0 * 128 * 1000 + 2.0 * 20 * 128)


def test_the_scopes_the_metrics_read_are_the_model_s():
    """``scope_roofline`` matches one scope name at any depth: ``selective``
    round the whole mixer, its projections ``attn_proj`` / ``attn_out`` as
    the other recurrences' are, and what a slot keeps under
    ``selective_step`` or ``selective_scan`` by the dispatch's length."""
    import dynamo_tpu.models.hybrid_linear as hybrid

    src = open(hybrid.__file__).read()
    body = src[src.index("def _selective"):src.index("def forward")]
    assert body.count('named_scope("selective")') == 1
    assert body.count('named_scope("attn_proj")') == 1
    assert body.count('named_scope("attn_out")') == 1
    assert '"selective_step" if s == 1 else "selective_scan"' in body
    assert (body.index('named_scope("selective")')
            < body.index('named_scope("attn_proj")')
            < body.index("named_scope(kept)"))
    assert 'named_scope("selective' not in src.replace(body, "")


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy jamba decoder (m m A m m m, 4 : 1 heads on one K/V head, a
    prefill chunk of 64) under the fixed-order generator with prompts of
    40-150 tokens in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("jamba"))
    shutil.copy(roots.HERE / "data" / "tiny-jamba.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason-long.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-jamba", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-jamba.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-jamba.reason", "config": "tiny-jamba",
        "traffic": "tiny-reason-long", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-jamba.reason"]
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
         str(keep / "phase.json"), "--workload", "tiny-jamba.reason",
         "--seed", str(2**31 + 62), "--seconds", "3", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, keep / "phase.json"


def test_tiny_cell_rehearses_and_counts_what_its_state_layers_did(rehearsed):
    """Nothing is asserted of any time.  Off the chip the three new metrics
    are left out (no peaks, and the CPU's profile names no scope) and
    everything the cell owes besides is reported; the counters are the ones
    the other recurrences' cells read, counted for the third."""
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
        16 * 128 * 4 + 3 * 128 * 4)
    resets = (core(after, "state_resets_total")
              - core(before, "state_resets_total"))
    assert abs(resets - out["attempted"]) <= 4 < resets
    tokens = (core(after, "state_tokens_total")
              - core(before, "state_tokens_total"))
    assert tokens % 5 == 0 and tokens >= 5 * 40 * out["attempted"]
    # a model without experts counts none
    assert core(after, "moe_experts_touched_total") == 0
