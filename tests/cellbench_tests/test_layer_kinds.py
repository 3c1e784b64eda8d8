"""What PR 35 made room for, with no model of that kind in the benchmark yet:

a stack in which only some layers attend (``attention_layers`` in the
configuration file: the two attention cost modules count those layers and no
others); a decode-attention kernel of another name (the configuration's
``kernels`` block names the operations to time and the cost module to load
for a roofline metric that is there); and the ``#`` line that says which
owed metric a traced run on the chip could not read, and why.

The traces are made up, as in ``test_tp4_cell.py``; the data root is a copy to
which files are added and in which none is edited."""

import json

import pytest

import roots
from cellbench import run, spec

CTXS = [97, 700, 3071, 4096, 33]
CHUNKS = [(512, 0), (188, 512), (512, 0), (512, 512), (512, 1024),
          (512, 1536), (512, 2048), (512, 2560), (33, 0)]
# (ops, bytes) of those calls by the code of the commit before PR 35
PINNED = {
    "decode_attention": {
        "mistral-7b": (3144548352.0, 788103168.0),
        "qwen3-30b-a3b": (1048182784.0, 131678208.0),
        "mistral-7b-tp4": (4192731136.0, 1050804224.0)},
    "prefill_attention": {
        "mistral-7b": (1952725991424.0, 2675539968.0),
        "qwen3-30b-a3b": (650908663808.0, 695287808.0),
        "mistral-7b-tp4": (2603634655232.0, 3567386624.0)},
}
CALLS = {"decode_attention": CTXS, "prefill_attention": CHUNKS}


def config_of(name):
    entry = next(c for c in spec.load_benchmark(roots.REPO)["configs"]
                 if c["name"] == name)
    return spec.read_json(roots.REPO / entry["file"])


def test_prefill_chunks_are_the_pinned_calls():
    chunks = spec.load_module(roots.REPO, "costs", "prefill_attention").chunks
    assert chunks(700, 512) + chunks(3072, 512) + chunks(33, 512) == CHUNKS


@pytest.mark.parametrize("config", sorted(PINNED["decode_attention"]))
@pytest.mark.parametrize("module", sorted(PINNED))
def test_accepted_configurations_cost_what_they_cost(module, config):
    cfg = config_of(config)
    assert "attention_layers" not in cfg and "kernels" not in cfg
    cost = spec.load_module(roots.REPO, "costs", module)
    assert cost.cost(cfg, CALLS[module]) == PINNED[module][config]   # to the bit


@pytest.mark.parametrize("module", sorted(PINNED))
def test_one_attending_layer_in_eleven_is_an_eleventh_of_the_work(module):
    cost = spec.load_module(roots.REPO, "costs", module)
    cfg = {**config_of("mistral-7b"), "num_hidden_layers": 11}
    all_attend = cost.cost(cfg, CALLS[module])
    one_attends = cost.cost({**cfg, "attention_layers": 1}, CALLS[module])
    assert all_attend == tuple(11 * x for x in one_attends)      # exactly
    assert cost.cost({**cfg, "attention_layers": 11}, CALLS[module]) == all_attend
    # which tokens and chunks fall into the slice does not depend on the key
    records = [{"prompt_len": 700, "first": 1.0, "token_times": [1.0, 1.5, 2.0]}]
    assert (cost.calls(records, (0.0, 3.0), cfg)
            == cost.calls(records, (0.0, 3.0), {**cfg, "attention_layers": 1}))


# ------------------------------------------------ a made-up traced run
LATENT_COST = '''"""Decode attention over a compressed cache: a made-up cost function."""


def calls(records, interval, config):
    t0, t1 = interval
    return [r["prompt_len"] + j for r in records
            for j, t in enumerate(r["token_times"]) if j >= 1 and t0 <= t < t1]


def cost(config, ctxs):
    layers = config.get("attention_layers", config["num_hidden_layers"])
    return 0.0, float(layers * config["latent_width"] * 2 * sum(ctxs))
'''


def record(index, due, prompt_len, token_times):
    return {"index": index, "due": due, "sent": due, "first": token_times[0],
            "token_times": token_times, "prompt_len": prompt_len,
            "status": "ok", "n_tokens": len(token_times),
            "max_tokens": len(token_times), "finish_reason": "length",
            "bad_tokens": 0}


@pytest.fixture(scope="module")
def kinds(tmp_path_factory):
    """A copy of the data with three configurations of 11 layers and their
    cells added as new files: ``all-attend``; ``some-attend`` (1 attending
    layer, the benchmark's own kernels); ``latent`` (its ``kernels`` block
    names another pattern and a cost module of its own for
    ``kernel.decode_attn_roofline``)."""
    root = roots.build(tmp_path_factory.mktemp("kinds"))
    before = {p: p.read_bytes() for p in (root / "cellbench").rglob("*")
              if p.is_file()}
    cb = root / "cellbench"
    base = {**json.loads((cb / "configs" / "tiny-dense.json").read_text()),
            "dtype": "bfloat16", "num_hidden_layers": 11}
    (cb / "configs" / "some-attend.json").write_text(json.dumps(
        {**base, "attention_layers": 1}))
    (cb / "configs" / "latent.json").write_text(json.dumps({
        **base, "latent_width": 576,
        "kernels": {"kernel.decode_attn_roofline": {
            "pattern": "^latent_decode", "cost": "latent_decode"}}}))
    (cb / "configs" / "all-attend.json").write_text(json.dumps(base))
    (cb / "costs" / "latent_decode.py").write_text(LATENT_COST)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("some-attend", "latent", "all-attend"):
        bench["configs"].append({"name": name, "source": "test", "reduced": [],
                                 "file": f"cellbench/configs/{name}.json",
                                 "why": "added by a test"})
        bench["workloads"].append({"name": f"{name}.closed", "config": name,
                                   "traffic": "tiny-closed", "chips": 1,
                                   "why": "added by a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(path.read_bytes() == data for path, data in before.items())
    return root


def made_up_ctx(root, cell, op_seconds):
    """What ``run.run`` hands the readers after a traced run on the chip: two
    requests, a 2 s slice in a 10 s window, self time by operation name."""
    records = [record(0, 1.0, 100, [1.5, 4.2, 4.6, 5.0, 5.4]),
               record(1, 4.0, 700, [4.5, 4.9, 5.3])]
    return {"records": records, "window": (0.0, 10.0), "edges": ({}, {}),
            "samples": [], "trace_dir": None, "trace_interval": (4.0, 6.0),
            "trace": {"op_seconds": op_seconds, "idle_pct": 12.5},
            "root": root, "device": {"platform": "tpu"}, "config": cell.config,
            "chips": cell.chips, "peaks": spec.load_peaks(root, "TPU v5 lite")}


OPS = {"paged_decode_attention_mq": 4e-5, "paged_prefill_attention_fused": 2e-5,
       "latent_decode_kernel": 5e-5, "fusion.3": 0.8}


def test_an_attending_layer_in_eleven_reads_an_eleventh_of_the_share(kinds):
    got = {}
    for name in ("all-attend.closed", "some-attend.closed"):
        cell = spec.load_cell(kinds, name)
        got[name] = run.layer_metrics(kinds, cell, made_up_ctx(kinds, cell, OPS))
    for metric in ("kernel.decode_attn_roofline", "kernel.prefill_attn_roofline"):
        whole = got["all-attend.closed"][metric]["value"]
        assert 0 < whole < 100
        assert got["some-attend.closed"][metric]["value"] == pytest.approx(
            whole / 11, rel=1e-12)


def test_a_configuration_names_its_kernel_and_its_cost_module(kinds):
    plain = spec.load_cell(kinds, "all-attend.closed")
    latent = spec.load_cell(kinds, "latent.closed")
    desc = spec.load_layer_metric(kinds, "kernel.decode_attn_roofline")
    # without the block the metric file's own arguments hold
    assert run.metric_args(desc, plain.config) == desc["args"] == {
        "pattern": "^paged_decode_attention", "cost": "decode_attention"}
    assert run.metric_args(desc, latent.config) == {
        "pattern": "^latent_decode", "cost": "latent_decode"}
    # and the block is for the metric it names, no other
    prefill = spec.load_layer_metric(kinds, "kernel.prefill_attn_roofline")
    assert run.metric_args(prefill, latent.config) == prefill["args"]

    ctx = made_up_ctx(kinds, latent, OPS)
    m = run.layer_metrics(kinds, latent, ctx)
    # decode tokens of the slice: request 0 at contexts 101..104, request 1 at
    # 701, 702; 11 layers x 576 x 2 B each, against 819 GB/s, in 50 us
    nbytes = 11 * 576 * 2 * (101 + 102 + 103 + 104 + 701 + 702)
    assert m["kernel.decode_attn_roofline"]["value"] == pytest.approx(
        100.0 * nbytes / 819e9 / 5e-5)
    assert m["kernel.decode_attn_roofline"]["unit"] == "%"
    # the same trace under the plain configuration times the other kernel
    # with the benchmark's own cost module
    cost = spec.load_module(kinds, "costs", "decode_attention")
    ops, nb = cost.cost(plain.config, cost.calls(ctx["records"], (4.0, 6.0), plain.config))
    p = run.layer_metrics(kinds, plain, made_up_ctx(kinds, plain, OPS))
    assert p["kernel.decode_attn_roofline"]["value"] == pytest.approx(
        100.0 * max(ops / 197e12, nb / 819e9) / 4e-5)
    # the prefill metric is untouched by the block: equal in both
    assert (m["kernel.prefill_attn_roofline"] == p["kernel.prefill_attn_roofline"])


def lines_of(capsys):
    return [l.split("] ", 1)[1] for l in capsys.readouterr().out.splitlines()
            if l.startswith("# [") and "not reported: " in l]


def test_an_owed_metric_that_is_not_read_says_why(kinds, capsys):
    """The refusal of PR 34: a kernel of another name matched nothing, the
    metric was left out without a word and the driver demanded it."""
    cell = spec.load_cell(kinds, "all-attend.closed")
    ops = {k: v for k, v in OPS.items() if not k.startswith("paged_decode")}
    m = run.layer_metrics(kinds, cell, made_up_ctx(kinds, cell, ops))
    assert "kernel.decode_attn_roofline" not in m
    assert "kernel.prefill_attn_roofline" in m and "device.idle_pct" in m
    said = {l.split(" ", 3)[2]: l for l in lines_of(capsys)}
    assert said["kernel.decode_attn_roofline"] == (
        "not reported: kernel.decode_attn_roofline (reader kernel_roofline): "
        "no device operation matched '^paged_decode_attention'")
    assert "kernel.prefill_attn_roofline" not in said
    # a reader without words of its own is named all the same
    assert said["http.queue_wait_ms"].endswith(
        "(reader prom_hist_mean): it found nothing to read")
    # every owed metric is either on the line or in the log, never neither
    owed = {x["name"] for x in spec.metrics_for(kinds, cell.name, "per_layer")}
    assert owed == set(m) | set(said) and not set(m) & set(said)


@pytest.mark.parametrize("change, why", [
    ({"trace_interval": (8.0, 9.0)}, "costs/decode_attention.py found no call in the slice"),
    ({"peaks": None}, "no peaks for this device"),
    ({"trace": {}}, "no profile of a slice"),
])
def test_each_thing_a_roofline_can_miss_has_its_words(kinds, capsys, change, why):
    cell = spec.load_cell(kinds, "all-attend.closed")
    ctx = {**made_up_ctx(kinds, cell, OPS), **change}
    m = run.layer_metrics(kinds, cell, ctx)
    assert "kernel.decode_attn_roofline" not in m
    assert any(l == "not reported: kernel.decode_attn_roofline "
               f"(reader kernel_roofline): {why}" for l in lines_of(capsys))


def test_off_the_chip_a_rehearsal_stays_quiet(kinds, capsys):
    cell = spec.load_cell(kinds, "all-attend.closed")
    ctx = {**made_up_ctx(kinds, cell, OPS), "device": {"platform": "cpu"},
           "peaks": None}
    m = run.layer_metrics(kinds, cell, ctx)
    assert not any(n.endswith("_roofline") for n in m) and "device.idle_pct" in m
    assert lines_of(capsys) == []
