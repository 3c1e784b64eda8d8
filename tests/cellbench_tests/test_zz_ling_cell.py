"""What the Ling-3.0-flash cell adds to the benchmark, as new files alone: a
configuration, a reference, three cost modules, five per-layer metrics and a
cell on the traffic file that was there — and a tiny rehearsal of generator
+ model + reference end to end in a copied root.  Every entry is found by its
name, none by its place in a list.  Nothing here depends on how fast the
machine is.  (Named to sort last: a new file here changes which files the six
workers of a whole run hold side by side, ROADMAP R1 (11).)"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import roots
from cellbench import spec

CELL = "ling-3.0-flash-ep4.reason-long-closed"
CONFIG = "ling-3.0-flash-ep4"
NEW_METRICS = {
    "device.delta_rule_pct": ("scope_share", "tok_s_chip"),
    "device.latent_attn_pct": ("scope_share", "tok_s_chip"),
    "kernel.delta_update_roofline": ("kernel_roofline", "itl_p95_ms"),
    "moe.held_touched_pct": ("counter_ratio", "tok_s_chip"),
    "moe.rows_per_held_expert": ("counter_ratio", "itl_p95_ms"),
}
# accepted metrics that list one cell and that the tests of that cell hold to
# it: the share of picks on held experts is Mistral-Small-4's, the
# recurrence's scope inside the mixer's is Solar's.  Neither is owed here
KEPT_AS_THEY_WERE = {
    "moe.held_pick_pct": ["mistral-small-4-ep8.docs-shared-closed"],
    "device.linear_attn_pct": ["solar-open2-ep16.reason-long-closed"]}


def entries() -> tuple[dict, dict, list[dict]]:
    """The configuration's, the cell's and the five metrics' entries."""
    bench = spec.load_benchmark(roots.REPO)
    (cfg,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    return cfg, cell, [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]


def test_files_load_by_name_and_the_traffic_is_the_file_that_is_there():
    root = roots.REPO
    cell = spec.load_cell(root, CELL)
    cfg, traffic = cell.config, cell.traffic
    assert cell.chips == 1 and not cell.params      # no cell file: closed loop
    solar = spec.load_cell(root, "solar-open2-ep16.reason-long-closed")
    assert traffic == solar.traffic                 # the same file, untouched
    assert traffic["clients"] == cfg["serve"]["max_batch_size"] == 64
    # the check's 700-token prompt crosses a chunk boundary
    assert cfg["serve"]["prefill_chunk_tokens"] == 512 < 700
    assert len(cfg["assumed"]) >= 12 and "28" in cfg["deployment"]
    for part in ("reduced_why", "serve_why", "check_why"):
        assert len(cfg[part]) > 200, part
    assert hasattr(spec.load_module(root, "reference", cfg["reference"]),
                   "make_forward")
    bench = spec.load_benchmark(root)
    # (a later PR appends behind these: nothing here says "last")
    assert [w["name"] for w in bench["workloads"]].count(CELL) == 1
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-ep4")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "cellbench/configs/ling-3.0-flash-ep4.json"
    # the five, appended together, each owed here alone
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("device.delta_rule_pct")
    assert names[at:at + 5] == list(NEW_METRICS)
    for name, (reader, moves) in NEW_METRICS.items():
        listed = [m for m in spec.metrics_for(root, CELL, "per_layer")
                  if m["name"] == name]
        assert listed and listed[0]["workloads"] == [CELL]
        metric = spec.load_layer_metric(root, name)
        assert metric["reader"] == reader
        assert metric["moves"] == listed[0]["moves"] == moves
        for key in ("unit", "better", "source", "layer"):
            assert metric[key] == listed[0][key], (name, key)
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    assert all(name not in {m["name"] for m in spec.metrics_for(
        root, w, "per_layer")} for w in others for name in NEW_METRICS)
    owed = {m["name"] for m in spec.metrics_for(root, CELL, "per_layer")}
    assert not set(KEPT_AS_THEY_WERE) & owed


def test_the_entries_keep_to_the_benchmark_s_contract():
    """What the task's contract holds an appended entry to, for this
    cell's entries by name."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    cfg, cell, metrics = entries()
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == CELL and cell["config"] == cfg["name"]
    assert cell["chips"] == 1 and cell["traffic"] == "reason-long-closed"
    for text in (cfg["why"], cell["why"], cfg["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    for key in cfg["reduced"]:      # a width is never cut
        assert name.match(key) and not re.search(
            r"(hidden_size|intermediate|_dim$|_rank$|head_dim|experts_per_tok)",
            key), key
    real = spec.load_benchmark(roots.REPO)
    e2e = {m["name"] for m in real["end_to_end"]}
    assert len(metrics) == len(NEW_METRICS)
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert name.match(m["name"]) and m["moves"] in e2e
        assert m["name"].endswith("_roofline") == ("roofline" in m["name"])
        assert m["layer"] in {x["layer"] for x in real["per_layer"]
                              if x["name"] not in NEW_METRICS}
    assert len(json.dumps(real, indent=1)) <= 64 * 1024
    assert len({m["name"] for m in real["per_layer"]}) == len(real["per_layer"])
    for listed in (m for m in real["per_layer"] if m["name"] in KEPT_AS_THEY_WERE):
        assert listed["workloads"] == KEPT_AS_THEY_WERE[listed["name"]]


def test_the_scopes_and_counters_the_metrics_read_are_the_model_s():
    """``scope_share`` matches one scope name at any depth: ``delta_rule``
    round the whole KDA mixer (``linear`` and ``linear_state``, which Solar's
    metrics read, stay inside it), ``latent_attn`` round the whole MLA mixer;
    the roofline's pattern is the state kernel's name; the two ratios read
    counts the engine exports."""
    import dynamo_tpu.models.hybrid_linear as hybrid
    from dynamo_tpu.obs.metric_names import ENGINE_COUNTS
    from dynamo_tpu.ops.pallas import linear_state, mla_dense_attention

    src = open(hybrid.__file__).read()
    for name in ("device.delta_rule_pct", "device.latent_attn_pct"):
        scope = spec.load_layer_metric(roots.REPO, name)["args"]["scope"]
        assert src.count(f'jax.named_scope("{scope}")') == 1, scope
    assert src.index('named_scope("delta_rule")') < src.index(
        'named_scope("linear")') < src.index('named_scope("linear_state")')
    assert 'jax.named_scope("attn"), jax.named_scope("linear")' in src
    assert 'jax.named_scope("dense_mlp")' in src
    pattern = spec.load_layer_metric(
        roots.REPO, "kernel.delta_update_roofline")["args"]["pattern"]
    assert pattern == "^linear_state_update"
    assert 'name="linear_state_update"' in open(linear_state.__file__).read()
    kernels = spec.read_json(
        roots.REPO / entries()[0]["file"])["kernels"]
    dense_src = open(mla_dense_attention.__file__).read()
    for block in kernels.values():
        assert f'name="{block["pattern"][1:]}"' in dense_src
    keys = {c.key for c in ENGINE_COUNTS}
    for name in ("moe.rows_per_held_expert", "moe.held_touched_pct"):
        args = spec.load_layer_metric(roots.REPO, name)["args"]
        for side in ("num", "den"):
            assert args[side].removeprefix("core.") in keys
    touched = spec.load_layer_metric(roots.REPO, "moe.held_touched_pct")
    assert touched["args"]["scale"] == 100 / 128
    rows = spec.load_layer_metric(roots.REPO, "moe.rows_per_held_expert")
    held = spec.load_layer_metric(roots.REPO, "moe.rows_per_expert")
    # the accepted metric's counts, over this cut's 128 held in place of 16
    assert rows["args"] == {**held["args"], "scale": 1 / 128}


def test_costs_are_the_hand_counts():
    """A decode row's state update: 6 layers x (2 x 32 x 128 x 128 x 4 B of
    state + 5 rows of 4,096 and 32 steps in float32), 7 operations a state
    element, prefill chunks left out; the attention rooflines count the one
    MLA layer of seven, a seventh of what the every-layer modules count."""
    cfg = spec.read_json(roots.REPO / entries()[0]["file"])
    cost = spec.load_module(roots.REPO, "costs", "delta_update")
    state = 32 * 128 * 128
    ops, nbytes = cost.cost(cfg, [("d",)])
    assert ops == 6 * 7 * state
    assert nbytes == 6 * (2 * state * 4 + 4 * (5 * 4096 + 32))
    # 64 rows: 1.6 GB a step, the issue's "read and written"
    assert 1.6e9 < cost.cost(cfg, [("d",)] * 64)[1] < 1.65e9
    records = [{"prompt_len": 1300, "first": 1.5, "token_times": [1.5, 1.6, 2.5]},
               {"prompt_len": 600, "first": 9.0, "token_times": [9.0, 9.1]}]
    assert cost.calls(records, (1.0, 2.0), cfg) == [("d",)]
    for phase, calls in (("decode", [1500, 3000]),
                         ("prefill", [(512, 0), (512, 512), (276, 1024)])):
        every = spec.load_module(roots.REPO, "costs", f"mla_dense_{phase}")
        one = spec.load_module(roots.REPO, "costs", f"mla_dense_layers_{phase}")
        assert one.calls is every.calls
        ops7, bytes7 = every.cost(cfg, calls)
        ops1, bytes1 = one.cost(cfg, calls)
        assert ops7 == pytest.approx(7 * ops1) and bytes7 == pytest.approx(7 * bytes1)
    # a decode row at context 2,000: 2,000 rows of 576 bf16, 32 heads'
    # queries and outputs, 2 x 32 x (576 + 512) operations a row
    ops, nbytes = spec.load_module(
        roots.REPO, "costs", "mla_dense_layers_decode").cost(cfg, [2000])
    assert ops == 2.0 * 32 * (576 + 512) * 2000
    assert nbytes == 2 * (576 * 2000 + 32 * (576 + 512))


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy ling_hybrid_mla decoder (layer 0 and one later period K K M of a
    six-layer stack, chunk 64) under the fixed-order generator with prompts
    of 40-150 tokens in a copied root."""
    root = roots.build(tmp_path_factory.mktemp("ling"))
    shutil.copy(roots.HERE / "data" / "tiny-ling-hybrid.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-reason-long.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-ling-hybrid", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-ling-hybrid.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-ling-hybrid.reason", "config": "tiny-ling-hybrid",
        "traffic": "tiny-reason-long", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = ["tiny-ling-hybrid.reason"]
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
         str(keep / "phase.json"), "--workload", "tiny-ling-hybrid.reason",
         "--seed", str(2**31 + 66), "--seconds", "3", "--trace", "1",
         "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)
    return p, keep / "phase.json"


def test_tiny_cell_rehearses_and_counts_what_each_kind_of_layer_did(rehearsed):
    """The ramp (8 s) holds on a slow machine: nothing is asserted of any
    time.  Off the chip the trace-read metrics are left out (no peaks, and
    the CPU's profile names no scope); the two counter ratios and everything
    the cell owes besides are reported."""
    p, kept = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles_in_window: 0" in l for l in lines)
    m = out["metrics"]
    assert set(NEW_METRICS) & set(m) == {"moe.rows_per_held_expert",
                                         "moe.held_touched_pct"}
    assert "moe.held_pick_pct" not in m
    assert 0 < m["moe.held_touched_pct"]["value"] * 1.28 <= 4
    # picks held a call over the metric's 128: the toy's 4 held make it small
    assert 0 < m["moe.rows_per_held_expert"]["value"] < 64 * 2 / 128
    assert m["kv.cut_short_pct"]["value"] == 0
    for owed in ("engine.ttft_ms", "engine.turn_wait_ms", "engine.prefill_ms",
                 "http.queue_wait_ms", "sched.decode_rows_per_dispatch"):
        assert owed in m, owed
    before, after = json.loads(kept.read_text())["edges"]
    core = lambda edge, key: edge["core." + key]
    grew = lambda key: core(after, key) - core(before, key)
    assert core(after, "state_position_mismatches_total") == 0
    assert core(after, "prefix_reuse") == 0
    assert core(after, "state_layers") == 3 and core(after, "cache_layers") == 1
    # a request starts from zeros (the window's edges cut at most a batch)
    assert abs(grew("state_resets_total") - out["attempted"]) <= 4 < grew(
        "state_resets_total")
    # three KDA layers saw every real token
    assert grew("state_tokens_total") >= 3 * 40 * out["attempted"]
