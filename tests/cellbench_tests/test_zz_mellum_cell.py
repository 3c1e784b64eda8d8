"""What the Mellum2 cell adds to the benchmark, as new files alone: a
configuration, a reference, four cost modules, five per-layer metrics and a
cell — and a tiny rehearsal of generator + model + reference end to end in a
copied root.  Nothing here depends on how fast the machine is: the
rehearsal's ramp is long enough for both documents' first asks on a slow one,
and it asserts counts, not times."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import roots
from cellbench import server, spec

CELL = "mellum2-12b-a2.5b.docs-shared-closed"
# in the order ISSUE 60 gives them; their place in ``per_layer`` is whatever
# later PRs leave it (a test that pinned "the last entries" failed for every
# PR after its own)
NEW_METRICS = ("kernel.window_decode_roofline", "kernel.window_prefill_roofline",
               "device.attn_window_pct", "device.attn_full_pct",
               "attn.window_walked_pct")
# the published language model (config.json of the source), by hand
PUBLISHED = {
    "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "num_experts": 64, "num_experts_per_tok": 8,
    "moe_intermediate_size": 896, "intermediate_size": 7168,
    "sliding_window": 1024, "vocab_size": 98304, "num_hidden_layers": 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "rms_norm_eps": 1e-6, "norm_topk_prob": True, "use_sliding_window": True,
    "tie_word_embeddings": False, "attention_bias": False,
    "model_type": "mellum", "hidden_act": "silu"}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def cost(name):
    return spec.load_module(roots.REPO, "costs", name)


def config():
    return spec.load_cell(roots.REPO, CELL).config


def test_files_load_by_name_and_state_the_cut():
    cell = spec.load_cell(roots.REPO, CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["generator"] == "shared_docs"
    assert cell.traffic["clients"] == 32
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == {"num_hidden_layers": 8}.get(key, value), key
    assert cfg["layer_types"] == PERIOD * 2          # two whole periods
    assert cfg["mlp_layer_types"] == ["sparse"] * 8
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert cfg["architectures"] == ["MellumForCausalLM"]
    assert "pipeline stages" in cfg["deployment"] and len(cfg["assumed"]) >= 6
    assert "13.1 of 15.75 GiB" in cfg["reduced_why"]
    assert hasattr(spec.load_module(roots.REPO, "reference", cfg["reference"]),
                   "make_forward")
    bench = spec.load_benchmark(roots.REPO)
    entry = next(c for c in bench["configs"] if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_every_kernel_pattern_matches_a_name_the_kernels_carry():
    """The two rooflines every cell reports read both forms of both kernels
    here; the two this cell adds read the windowed form alone."""
    import re

    from dynamo_tpu.ops.pallas import decode_attention, prefill_attention

    cfg = config()
    names = {
        "decode": ["paged_decode_attention_mq",
                   "paged_decode_attention_window_mq"],
        "prefill": ["paged_prefill_attention", "paged_prefill_attention_window",
                    "paged_prefill_attention_ragged",
                    "paged_prefill_attention_window_ragged"]}
    # the names as the kernels' sources spell them (the prefill pair's are
    # put together from their parts)
    src = open(decode_attention.__file__).read()
    assert all(f'"{n}"' in src for n in names["decode"])
    src = open(prefill_attention.__file__).read()
    assert all(f'"{part}"' in src
               for part in ("paged_prefill_attention", "_window", "_ragged"))
    both = cfg["kernels"]
    assert set(both) == {"kernel.decode_attn_roofline",
                         "kernel.prefill_attn_roofline"}
    for phase, metric, alone in (
            ("decode", "kernel.decode_attn_roofline",
             "kernel.window_decode_roofline"),
            ("prefill", "kernel.prefill_attn_roofline",
             "kernel.window_prefill_roofline")):
        wide = re.compile(both[metric]["pattern"])
        narrow = re.compile(
            spec.load_layer_metric(roots.REPO, alone)["args"]["pattern"])
        assert all(wide.search(n) for n in names[phase])
        assert ([n for n in names[phase] if narrow.search(n)]
                == [n for n in names[phase] if "_window" in n])
        assert hasattr(cost(both[metric]["cost"]), "cost")
        assert hasattr(cost(spec.load_layer_metric(
            roots.REPO, alone)["args"]["cost"]), "calls")


def test_the_five_new_metrics_are_declared_in_the_issues_order():
    """Among themselves; where they stand in ``per_layer`` is not this
    test's to say."""
    listed = [m for m in spec.metrics_for(roots.REPO, CELL, "per_layer")
              if m["name"] in NEW_METRICS]
    assert [m["name"] for m in listed] == list(NEW_METRICS)
    for m in listed:
        assert m["workloads"] == [CELL] and m["layer"] == "kernels"
        assert m["unit"] == "%"
    readers = [spec.load_layer_metric(roots.REPO, n)["reader"]
               for n in NEW_METRICS]
    assert readers == ["kernel_roofline", "kernel_roofline", "scope_share",
                       "scope_share", "counter_ratio"]
    moves = {m["name"]: m["moves"] for m in listed}
    assert moves["kernel.window_prefill_roofline"] == "ttft_mean_ms"
    assert set(moves.values()) == {"ttft_mean_ms", "itl_p95_ms"}
    scopes = [spec.load_layer_metric(roots.REPO, n)["args"].get("scope")
              for n in NEW_METRICS[2:4]]
    assert scopes == ["window", "full"]


def test_weights_and_cache_are_the_stated_size():
    """3.795 B parameters = 7.07 GiB and 12,288 blocks of 32 tokens x 8
    layers x 2 KiB = 6.0 GiB, from the program's own shapes: 83% of the
    chip."""
    cfg = config()
    mcfg = server.model_config(cfg)
    assert mcfg.period == tuple(PERIOD) and mcfg.window_layers == 6
    model = server.resolve(cfg["model_class"])(mcfg)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    attention = 2 * 2304 * 4096 + 2 * 2304 * 512
    layer = (attention + 64 * 3 * 2304 * 896 + 2304 * 64   # experts, router
             + 2 * 2304 + 2 * 128)                          # norms, q/k norms
    assert n == 8 * layer + 2 * 98304 * 2304 + 2304
    assert 7.06 < 2 * n / 2**30 < 7.08
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"]))
    assert cache.shape == (8, 12288, 2, 32, 512)
    held = cache.size * 2
    assert held == 6 * 2**30
    assert 0.82 < (2 * n + held) / (15.75 * 2**30) < 0.84
    # the documents and 32 live rows' own tokens fit
    assert serve["num_blocks"] * 32 >= 4 * (16384 + 24576 + 32768) + 32 * 640
    assert serve["max_model_len"] >= 32768 + 256 + 384


def test_decode_costs_are_the_hand_count():
    """Two rows at contexts 500 and 24,000.  A window layer reads
    min(ctx, 1,024) rows: 500 + 1,024; a full layer all 24,500.  Per layer
    and row 4 x 32 heads x 128 operations and 2 x 4 heads x 128 bf16
    elements; per call and layer 2 x 32 x 128 elements of query and output."""
    cfg = config()
    both = cost("window_decode_attention")
    alone = cost("window_layers_decode_attention")
    w_rows, f_rows = 500 + 1024, 24500
    ops, nbytes = both.cost(cfg, [500, 24000])
    assert ops == 4 * 32 * 128 * (6 * w_rows + 2 * f_rows)
    assert nbytes == 2 * (2 * 4 * 128 * (6 * w_rows + 2 * f_rows)
                          + 8 * 2 * 32 * 128 * 2)
    ops_w, nbytes_w = alone.cost(cfg, [500, 24000])
    assert ops_w == 4 * 32 * 128 * 6 * w_rows
    assert nbytes_w == 2 * 6 * (2 * 4 * 128 * w_rows + 2 * 32 * 128 * 2)
    # the step of the issue: 32 rows at ~24.9 k read 0.40 GB in six window
    # layers where full layers would read 9.8
    _, at_step = alone.cost(cfg, [24900] * 32)
    assert 0.40e9 < at_step < 0.42e9
    records = [{"prompt_len": 16384 + 100, "token_times": [0.5, 1.5, 2.5]}]
    assert both.calls(records, (1.0, 3.0), cfg) == [16485, 16486]
    assert alone.calls is both.calls


def test_prefill_costs_count_the_band_over_document_and_question():
    mod = cost("window_prefill_attention")
    alone = cost("window_layers_prefill_attention")
    cfg = config()
    # no window: the causal triangle over the prefix
    assert mod.attended(None, 3, 0) == (1 + 2 + 3, 3)
    assert mod.attended(None, 4, 10) == (11 + 12 + 13 + 14, 14)
    # a window of 8: queries at 10..13 see 8 rows each, of rows 3..13
    assert mod.attended(8, 4, 10) == (4 * 8, 7 + 4)
    # the window fills inside the chunk: queries at 5..9 see 6, 7, 8, 8, 8
    assert mod.attended(8, 5, 5) == (6 + 7 + 8 + 8 + 8, 5 + 5)
    # a cold prompt's first tokens: 1, 2, 3 rows
    assert mod.attended(8, 3, 0) == (6, 3)
    records = [{"prompt_len": 24576 + 130, "first": 1.5},
               {"prompt_len": 16384 + 64, "first": 9.0}]
    calls = mod.calls(records, (1.0, 2.0), cfg)
    assert calls == [(130, 24576)]          # the question after the document
    ops, nbytes = mod.cost(cfg, calls)
    full = 130 * 24576 + 130 * 131 // 2
    band = 130 * 1024
    assert ops == 4 * 32 * 128 * (6 * band + 2 * full)
    assert nbytes == 2 * (
        6 * (2 * 4 * 128 * (1023 + 130) + 2 * 32 * 128 * 130)
        + 2 * (2 * 4 * 128 * (24576 + 130) + 2 * 32 * 128 * 130))
    ops_w, nbytes_w = alone.cost(cfg, calls)
    assert ops_w == 4 * 32 * 128 * 6 * band
    assert nbytes_w == 2 * 6 * (2 * 4 * 128 * 1153 + 2 * 32 * 128 * 130)
    assert alone.calls is mod.calls


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """A toy mellum decoder (window 64, two periods of a window and a full
    layer) under the shared-documents generator (two 8,192-token documents,
    8-16-token questions) in a copied root; a 20 s ramp, because a slow
    machine needs ~15 s for both documents' first asks."""
    root = roots.build(tmp_path_factory.mktemp("mellum"))
    shutil.copy(roots.HERE / "data" / "tiny-mellum.json",
                root / "cellbench/configs")
    shutil.copy(roots.HERE / "data" / "tiny-docs-window.json",
                root / "cellbench/traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-mellum", "source": "test fixture", "reduced": [],
        "file": "cellbench/configs/tiny-mellum.json", "why": "toy"})
    bench["workloads"].append({
        "name": "tiny-mellum.docs", "config": "tiny-mellum",
        "traffic": "tiny-docs-window", "chips": 1, "why": "toy"})
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            m["workloads"] = ["tiny-mellum.docs"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(roots.REPO)}
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "tiny-mellum.docs", "--seed", str(2**31 + 60), "--seconds", "4",
         "--trace", "1", "--root", str(root), "--rehearse"],
        cwd=roots.REPO, env=env, capture_output=True, text=True, timeout=900)


def test_tiny_cell_rehearses_and_counts_the_window_walk(rehearsed):
    p = rehearsed
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    started = next(l for l in lines if l.startswith("# attention:"))
    assert "2 window layers of 64, 2 full" in started
    m = out["metrics"]
    # a decode row at ~8.2 k of context walks 64 / 16 + 1 of its ~513 blocks
    # in a window layer (4 where the band begins on a block)
    assert 4 / 514 * 100 <= m["attn.window_walked_pct"]["value"] <= 5 / 512 * 100
    assert m["kv.cut_short_pct"]["value"] == 0
    assert m["sched.ahead_dispatch_pct"]["value"] >= 90
