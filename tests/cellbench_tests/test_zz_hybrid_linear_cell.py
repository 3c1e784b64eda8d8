"""What the Solar-Open2 cell adds to the benchmark, as new files alone: a
configuration, a traffic mix, a reference, a cost module, two per-layer
metrics and a cell — and a tiny rehearsal of generator + model + reference
end to end in a copied root.  Nothing here depends on how fast the machine
is.  (Named to sort last: a new file here changes which files the six
workers of a whole run hold side by side, ROADMAP R1 (11).)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "solar-open2-ep16.reason-long-closed"
NEW_METRICS = {"kernel.linear_attn_roofline": ("scope_roofline", "linear_state"),
               "device.linear_attn_pct": ("scope_share", "linear")}


def test_files_load_by_name_and_the_traffic_is_the_issue_s():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1
    assert {k: traffic[k] for k in (
        "generator", "loop", "clients", "prompt_len", "output_len",
        "sampling", "ramp_s", "population_seed")} == {
        "generator": "mix_fixed_order", "loop": "closed", "clients": 64,
        "prompt_len": {"dist": "uniform", "min": 512, "max": 2048},
        "output_len": {"dist": "uniform", "min": 512, "max": 1024},
        "sampling": {"temperature": 0.7, "top_p": 0.9}, "ramp_s": 20,
        "population_seed": 0}
    assert traffic["clients"] == cfg["serve"]["max_batch_size"]
    # the check's 700-token prompt crosses a chunk boundary
    assert cfg["serve"]["prefill_chunk_tokens"] == 512 < 700
    assert cfg["attention_layers"] == len(cfg["gqa_layers"])
    assert len(cfg["assumed"]) >= 8 and "96" in cfg["deployment"]
    assert hasattr(spec.load_module(roots.REPO, "reference", cfg["reference"]),
                   "make_forward")
    assert "kernels" not in cfg        # the GQA layers use the default kernels
    for name, (reader, scope) in NEW_METRICS.items():
        listed = [m for m in spec.metrics_for(roots.REPO, CELL, "per_layer")
                  if m["name"] == name]
        assert listed and listed[0]["workloads"] == [CELL]
        metric = spec.load_layer_metric(roots.REPO, name)
        assert metric["reader"] == reader and metric["args"]["scope"] == scope
        assert metric["moves"] == "itl_p95_ms"
    others = [w["name"] for w in spec.load_benchmark(roots.REPO)["workloads"]
              if w["name"] != CELL]
    assert all(name not in {m["name"] for m in spec.metrics_for(
        roots.REPO, w, "per_layer")} for w in others for name in NEW_METRICS)


def test_the_scopes_the_metrics_read_are_the_model_s():
    """``scope_roofline`` matches one scope name at any depth: ``linear``
    round the whole mixer, ``linear_state`` inside it round the recurrence."""
    import dynamo_tpu.models.hybrid_linear as hybrid

    src = open(hybrid.__file__).read()
    assert 'jax.named_scope("attn"), jax.named_scope("linear")' in src
    assert src.count('jax.named_scope("linear_state")') == 1
    assert src.index('named_scope("linear")') < src.index(
        'named_scope("linear_state")')


def test_state_cost_is_the_hand_count():
    """A decode row: 6 layers x (2 x 64 x 128 x 128 x 4 B of state + 5 rows of
    8,192 and 64 steps in float32), 7 operations a state element.  A
    512-token chunk: the state once, its rows, eight chunks of the WY form."""
    cost = spec.load_module(roots.REPO, "costs", "linear_state")
    cfg = spec.load_cell(roots.REPO, CELL).config
    state = 64 * 128 * 128
    ops, nbytes = cost.cost(cfg, [("d",)])
    assert ops == 6 * 7 * state
    assert nbytes == 6 * (2 * state * 4 + 4 * (5 * 8192 + 64))
    # 64 rows: 3.2 GB a step, the issue's "read and written"
    assert 3.2e9 < cost.cost(cfg, [("d",)] * 64)[1] < 3.3e9
    ops, nbytes = cost.cost(cfg, [("p", 512)])
    per_chunk = 64 * (6 * 64 * 128 * 128 + 5 * 64 * 64 * 128 + 64 ** 3 / 3)
    assert ops == pytest.approx(6 * 8 * per_chunk)
    assert nbytes == 6 * (2 * state * 4 + 4 * (5 * 8192 + 64) * 512)
    assert cost.cost(cfg, [("p", 100)])[0] == pytest.approx(6 * 64 * (
        6 * 64 * 128 * 128 + 5 * 64 * 64 * 128 + 64 ** 3 / 3
        + 6 * 36 * 128 * 128 + 5 * 36 * 36 * 128 + 36 ** 3 / 3))
    # rebuilt from the client's records: decode tokens that arrived in the
    # slice, chunks of the prompts whose first token did
    records = [{"prompt_len": 1300, "first": 1.5, "token_times": [1.5, 1.6, 2.5]},
               {"prompt_len": 600, "first": 9.0, "token_times": [9.0, 9.1]}]
    assert sorted(cost.calls(records, (1.0, 2.0), cfg)) == [
        ("d",), ("p", 276), ("p", 512), ("p", 512)]


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy solar_open2 decoder (two periods G L L L, chunk 64) under the
    fixed-order generator with prompts of 40-150 tokens in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("hybrid"))
    shutil.copy(roots.HERE / "data" / "tiny-hybrid-linear.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason-long.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-hybrid-linear", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-hybrid-linear.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-hybrid-linear.reason", "config": "tiny-hybrid-linear",
        "traffic": "tiny-reason-long", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-hybrid-linear.reason"]
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
         str(keep / "phase.json"), "--workload", "tiny-hybrid-linear.reason",
         "--seed", str(2**31 + 29), "--seconds", "3", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, keep / "phase.json"


def test_tiny_cell_rehearses_and_counts_what_its_linear_layers_did(rehearsed):
    """The ramp (8 s) holds on a slow machine: nothing is asserted of any
    time.  Off the chip the two new metrics are left out (no peaks, and the
    CPU's profile names no scope) and everything the cell owes besides is
    reported."""
    p, kept = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert not set(NEW_METRICS) & set(m)
    assert m["sched.ahead_dispatch_pct"]["value"] > 50    # answers of 6-12
    assert m["kv.cut_short_pct"]["value"] == 0
    for owed in ("engine.ttft_ms", "engine.turn_wait_ms", "engine.prefill_ms",
                 "http.queue_wait_ms", "sched.decode_rows_per_dispatch"):
        assert owed in m, owed
    before, after = json.loads(kept.read_text())["edges"]
    core = lambda edge, key: edge["core." + key]
    assert core(after, "state_position_mismatches_total") == 0
    assert core(after, "prefix_reuse") == 0
    assert core(after, "state_layers") == 6 and core(after, "cache_layers") == 2
    resets = (core(after, "state_resets_total")
              - core(before, "state_resets_total"))
    # a request starts from zeros (the window's edges cut at most a batch)
    assert abs(resets - out["attempted"]) <= 4 < resets
    tokens = (core(after, "state_tokens_total")
              - core(before, "state_tokens_total"))
    assert tokens % 6 == 0 and tokens >= 6 * 40 * out["attempted"]
