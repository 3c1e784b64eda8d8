"""What PR 27 added for the four-chip cell: the reader of collective time on
a made-up trace, the closed loop whose order the seed does not choose, and a
``--rehearse`` run of a tiny ``"tp": 4`` configuration on four virtual
devices of the CPU, which reports the contract's line.

The three ``coll.*`` metrics are declared in the real ``BENCHMARK.json`` with
``"workloads": ["mistral-7b-tp4.chat-closed"]`` (PR 35): collectives exist
only across chips, so only that cell owes them.  The rehearsal's copy lists
its own four-device cell instead."""

import json
import shutil

import pytest

import roots
from cellbench import spec
from test_rehearse import CONTRACT_KEYS, run_cell

COLL = ("coll.time_pct", "coll.decode_program_ms", "coll.prefill_program_ms")
US = 1e3    # a trace's times are nanoseconds


def reader():
    return spec.load_module(roots.REPO, "readers", "trace_collective")


def read(name, t):
    desc = spec.load_layer_metric(roots.REPO, name)
    assert desc["reader"] == "trace_collective"
    return reader().reading(t, desc["args"])


def device(shift, slow=1.0):
    """Two decode programs and one prefill program on one device: a layer
    loop (``while``) that holds a matmul and an all-reduce a time, and after
    it the head's all-gather.  ``slow`` stretches this device's collectives:
    the wait for a peer is part of a collective's duration."""
    ops, modules = [], []
    t = shift
    for program, matmul, reduce_ in (("jit__multi_impl", 100, 10),
                                     ("jit__step_impl", 300, 40),
                                     ("jit__multi_impl", 100, 10)):
        start = t
        ops.append(["while", t, 2 * (matmul + reduce_ * slow) * US, "", program])
        for _ in range(2):
            ops.append(["fusion.1", t, matmul * US, "mlp", program])
            t += matmul * US
            ops.append(["all-reduce.3", t, reduce_ * slow * US, "mlp", program])
            t += reduce_ * slow * US
        ops.append(["all-gather-start.1", t, 2 * US, "sample", program])
        ops.append(["all-gather-done.1", t + 2 * US, 3 * slow * US, "sample", program])
        t += (2 + 3 * slow) * US
        modules.append([f"{program}(7)", start, t - start])
        t += 50 * US        # the host's turn
    ops.append(["fusion.9", t, 20 * US, "", "jit__threefry_split"])
    return {"ops": ops, "modules": modules}


def test_collective_time_on_two_devices_and_two_programs():
    t = {"devices": {"/device:TPU:0": device(0.0),
                     "/device:TPU:1": device(7.0, slow=2.0)}}
    # device 0: decode 2 x 10 + 2 + 3 = 25 us a run, prefill 2 x 40 + 5 = 85;
    # device 1, whose collectives take twice as long: 48 and 168
    assert read("coll.decode_program_ms", t) == pytest.approx((25 + 48) / 2 / 1e3)
    assert read("coll.prefill_program_ms", t) == pytest.approx((85 + 168) / 2 / 1e3)
    coll = 2 * 25 + 85 + 2 * 48 + 168
    busy = coll + 2 * (2 * 2 * 100 + 2 * 300 + 20)       # + matmuls, rng split
    assert read("coll.time_pct", t) == pytest.approx(100.0 * coll / busy)


def test_every_collective_name_counts_and_nothing_else():
    rx = reader().COLLECTIVE
    for name in ("all-reduce", "all-reduce.12", "all-reduce-start.3",
                 "all-reduce-done.3", "all-gather.1", "reduce-scatter.2",
                 "all-to-all", "collective-permute-start.4",
                 "collective-permute-done.4"):
        assert rx.match(name), name
    for name in ("fusion.12", "all-reduce-scatter_fusion", "reduce.5",
                 "paged_decode_attention_mq", "gather.3", "copy.21"):
        assert not rx.match(name), name


def test_one_chip_reads_zero_and_no_trace_reads_nothing():
    one = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 0.0, 100 * US, "mlp", "jit__multi_impl"]],
        "modules": [["jit__multi_impl(3)", 0.0, 100 * US]]}}}
    assert read("coll.time_pct", one) == 0.0
    assert read("coll.decode_program_ms", one) == 0.0
    assert read("coll.prefill_program_ms", one) is None    # no such program ran
    for nothing in (None, {"devices": {}},
                    {"devices": {"d": {"ops": [], "modules": []}}}):
        assert all(read(n, nothing) is None for n in COLL)
    ctx = {"trace_dir": None, "root": roots.REPO, "device": {"platform": "tpu"}}
    for n in COLL:
        assert reader().read(ctx, spec.load_layer_metric(roots.REPO, n)["args"]) is None


def test_metric_files_are_declared_for_the_four_chip_cell():
    bench = spec.load_benchmark(roots.REPO)
    e2e = {m["name"] for m in bench["end_to_end"]}
    declared = {m["name"]: m for m in bench["per_layer"]}
    for n in COLL:
        d = spec.load_layer_metric(roots.REPO, n)
        assert d["name"] == n and d["layer"] == "device" and d["moves"] in e2e
        assert d["source"] == "device_trace" and "workloads" not in d
        assert declared[n]["workloads"] == ["mistral-7b-tp4.chat-closed"]
        assert {k: declared[n][k] for k in declared[n] if k != "workloads"} == {
            k: d[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
    for cell in (w["name"] for w in bench["workloads"]):
        got = {m["name"] for m in spec.metrics_for(roots.REPO, cell, "per_layer")}
        owes = cell == "mistral-7b-tp4.chat-closed"
        assert (set(COLL) <= got) if owes else not (set(COLL) & got), cell


# ------------------------------------------- the closed loop's fixed order
def test_fixed_order_is_the_draw_whatever_the_seed():
    cell = spec.load_cell(roots.REPO, "mistral-7b-tp4.chat-closed")
    assert cell.traffic["generator"] == "mix_fixed_order"
    fixed = spec.load_module(roots.REPO, "generators", "mix_fixed_order")
    mix = spec.load_module(roots.REPO, "generators", "mix")
    a, b = (fixed.Schedule(cell.traffic, seed, 51.0, 32768)
            for seed in (7, 2**31 + 7))
    assert a.sizes == b.sizes == mix.draw_sizes(cell.traffic, 256)
    shuffled = mix.Schedule(cell.traffic, 7, 51.0, 32768)
    assert sorted(shuffled.sizes) == sorted(a.sizes) != shuffled.sizes
    for k in (0, 1, 255, 256, 300):
        ra, rb = a.request(k), b.request(k)
        assert (len(ra.prompt), ra.max_tokens) == a.sizes[k % 256]
        assert (len(rb.prompt), rb.max_tokens) == a.sizes[k % 256]
        assert ra.prompt != rb.prompt and ra.sampling == cell.traffic["sampling"]
    # a repeat of a size is not a repeat of a prompt
    assert a.request(0).prompt != a.request(256).prompt
    # the open loop is mix's own
    open_cell = spec.load_cell(roots.REPO, "mistral-7b.chat-open")
    mine, theirs = (g.Schedule(open_cell.traffic, 7, 51.0, 32768)
                    for g in (fixed, mix))
    assert mine.dues == theirs.dues and mine.sizes == theirs.sizes


# ------------------------------------ a rehearsal on four virtual devices
@pytest.fixture(scope="module")
def tp4(tmp_path_factory):
    root = roots.build(tmp_path_factory.mktemp("tp4"))
    shutil.copy(roots.HERE / "data" / "tiny-tp4.json",
                root / "cellbench" / "configs")
    shutil.copy(roots.HERE / "data" / "tiny-fixed-closed.json",
                root / "cellbench" / "traffic")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-tp4", "source": "test fixture",
                             "file": "cellbench/configs/tiny-tp4.json",
                             "reduced": [], "why": "toy, tensor parallel 4"})
    bench["workloads"].append({"name": "tiny-tp4.closed", "config": "tiny-tp4",
                               "traffic": "tiny-fixed-closed", "chips": 4,
                               "why": "closed loop, toy, four devices"})
    for m in bench["per_layer"]:    # across chips only: this copy's such cell
        if m["name"] in COLL:
            m["workloads"] = ["tiny-tp4.closed"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # conftest.py gave this process eight virtual devices through XLA_FLAGS,
    # and the run inherits them: the mesh takes the first four
    return root, {trace: run_cell(root, "tiny-tp4.closed", trace,
                                  extra=["--rehearse"]) for trace in (0, 1)}


@pytest.mark.parametrize("trace", [0, 1])
def test_tp4_rehearsal_prints_the_contract_line(tp4, trace):
    root, runs = tp4
    p, lines = runs[trace]
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(lines[-1])
    assert CONTRACT_KEYS <= set(out) <= CONTRACT_KEYS | {"breakdown"}
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4
    assert any("compiles_in_window: 0" in l for l in lines)
    attention = json.loads(next(
        l for l in lines if l.startswith("# attention: "))[len("# attention: "):])
    assert len(attention) == 4      # off the chip every phase is XLA's
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if trace:
        assert set(out["metrics"]) <= {m["name"] for m in bench["per_layer"]}
        assert {"step.wall_ms", "device.idle_pct", "coll.time_pct"} <= set(out["metrics"])
        assert 0.0 <= out["metrics"]["coll.time_pct"]["value"] <= 100.0
    else:
        assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
