"""The agreement of the served Mellum2 configuration with its reference at
contexts past its window, which the benchmark's ``correct`` cannot reach: its
prompts are 17-700 tokens (+ 8), inside the 1,024-token window, where a
window layer is a full layer and YaRN's slow pairs have hardly turned.

    chiprun --timeout 3000 -- python3 scripts/mellum_longctx_check.py \
        [--lengths 1504,4992,9024,20032] [--seed N] [--tiny]

One process, on the chip (``--tiny``: a toy size on the CPU, to rehearse the
script).  It builds cellbench/configs/mellum2-12b-a2.5b.json at its published
widths with seeded weights and serves it through ``EngineCore`` with the
cell's ``serve`` block.  A prompt is a document and a 64-token question; the
lengths put the prompt past the window (1,504), past a 2,048-token chunk with
the band's older edge inside a chunk and inside the cached prefix (4,992),
past YaRN's trained context of 8,192 (9,024) and at a document's length
(20,032).  For every length, 8 greedy tokens with their top-20
log-probabilities:

  A  alone, cold: chunked prefill (the windowed and the full flash kernel
     over a growing prefix), then decode steps;
  B  alone, the same document and a new question: a prefix hit of the whole
     document, the question's chunk over that past, the decode steps;
  C  together, the four documents again with new questions: four prefix
     hits in one batch, decode rows of 1.5 k to 20 k in one kernel group;
  D  together, four new documents: cold chunks interleaved with decode rows.

Each answer is set against cellbench/reference/mellum_swa_moe.py run over its
whole sequence (teacher-forced on the engine's own tokens), under the
configuration's own ``check`` rule, pooled by set (a set's four lengths) and
by length (a length's four runs).  Then four negative controls, each of
which must FAIL that rule on every length (all are past the window) and on
the set, against B's answers: the reference with every layer full; with
plain RoPE in the full layers (no YaRN, factor 1); with the window a block
too long; with K/V one precision down (keys and values rounded to float8
e4m3 as a cache would hold them).  The first three differ from the model in the layers' tables alone, so
they share its compiled program.

The last line is one JSON object with every margin; exit code 0 when every
set and every length passes, every prefix hit was whole and every control
fails.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# serving through EngineCore is the GLM script's
from scripts.glm_longctx_check import note, serve  # noqa: E402

CONFIG = ROOT / "cellbench/configs/mellum2-12b-a2.5b.json"
KV_ROUND = (4, 3)       # float8 e4m3: the nearest precision below bf16

TINY = dict(
    architectures=["MellumForCausalLM"], model_type="mellum",
    vocab_size=512, hidden_size=64, intermediate_size=256,
    num_hidden_layers=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    mlp_layer_types=["sparse"] * 8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    attention_bias=False, hidden_act="silu", moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4, "beta_fast": 4, "beta_slow": 1,
                           "original_max_position_embeddings": 64,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    sliding_window=32, use_sliding_window=True, max_window_layers=0,
    tie_word_embeddings=False, rms_norm_eps=1e-6,
    max_position_embeddings=4096, dtype="float32",
    reference="mellum_swa_moe",
    model_class="dynamo_tpu.models.llama:LlamaModel",
    config_class="dynamo_tpu.models.config:ModelConfig",
    serve={"max_batch_size": 4, "block_size": 8, "max_model_len": 512,
           "prefill_chunk_tokens": 64, "num_blocks": 256},
    check={"abs_tol": 0.05, "share_within": 0.99, "median_tol": 0.005})


def submit(core, name: str, prompt: list[int], n: int) -> dict:
    """One greedy request with its top-20 candidates, queued; ``drain``
    runs the engine until every queued request has answered."""
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    out = {"name": name, "prompt": list(prompt), "tokens": [], "top": []}

    def emit(o):
        out["tokens"] += list(o.token_ids)
        out["top"] += [dict(pairs) for pairs in (o.top_logprobs or [])]

    def allocated(req):
        out["cached"] = req.cached_tokens

    core.submit(EngineRequest(
        request_id=name, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0, logprobs=True,
                                 top_logprobs=20),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=emit, on_allocated=allocated))
    return out


def drain(core, answers: list[dict], n: int) -> None:
    while core.step():
        pass
    for a in answers:
        assert len(a["tokens"]) == n and len(a["top"]) == n, (
            a["name"], len(a["tokens"]), len(a["top"]))


def control_tables(ref, config: dict) -> dict:
    """name -> the layers' tables of a reference that got one thing wrong."""
    block = config["serve"]["block_size"]
    plain = {**config["rope_parameters"], "full_attention": {
        "rope_type": "default",
        "rope_theta":
            config["rope_parameters"]["full_attention"]["rope_theta"]}}
    edits = {
        "every_layer_full": {"sliding_window": ref.NO_WINDOW},
        "plain_rope_in_full_layers": {"rope_parameters": plain},
        "window_off_by_a_block": {
            "sliding_window": config["sliding_window"] + block},
    }
    return {name: ref.layer_tables({**config, **edit})
            for name, edit in edits.items()}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--lengths", default="1504,4992,9024,20032",
                   help="prompt lengths: a document and the question")
    p.add_argument("--seed", type=int, default=2**31 + 60)
    p.add_argument("--question", type=int, default=64)
    p.add_argument("--answer", type=int, default=8,
                   help="tokens generated a run: x 20 log-probabilities x 4 "
                        "lengths = the pairs a set is held to")
    p.add_argument("--num-blocks", type=int, default=None,
                   help="cache blocks of the pool (default: the cell's)")
    p.add_argument("--tiny", action="store_true",
                   help="rehearse at a toy size on the CPU")
    a = p.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cellbench import check, spec

    if a.tiny:
        config = TINY
        lengths, a.question = [56, 152, 200, 416], 16
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(CONFIG)
        lengths = [int(c) for c in a.lengths.split(",")]
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    note(f"compile cache: {enable_persistent_cache()}")
    model, core = serve(config, a.seed,
                        a.num_blocks or config["serve"]["num_blocks"])
    note("attention: " + json.dumps(
        {k: f"{v[0]} ({v[1]})" for k, v in core.attention_impls().items()}))
    ref = spec.load_module(ROOT, "reference", config["reference"])
    run = jax.jit(ref.make_run(config))
    run_fp8 = jax.jit(ref.make_run(config, kv_round=KV_ROUND))
    tables = ref.layer_tables(config)
    rule, vocab, n = config["check"], config["vocab_size"], a.answer

    def reference(ans, fn=run, tabs=tables):
        seq = ans["prompt"] + ans["tokens"]
        padded = np.zeros(-(-len(seq) // 128) * 128, np.int32)
        padded[:len(seq)] = seq
        rows = np.arange(len(ans["prompt"]) - 1, len(seq) - 1, dtype=np.int32)
        return np.asarray(fn(core.params, jnp.asarray(padded),
                             jnp.asarray(rows), tabs))

    def deltas(ans, logp):
        return [abs(lp - float(logp[pos][tid]))
                for pos, top in enumerate(ans["top"])
                for tid, lp in top.items()]

    rng = np.random.default_rng(a.seed)
    ids = lambda k: rng.integers(1, vocab, k).tolist()
    docs = {L: ids(L - a.question) for L in lengths}
    sets, ok = {}, True
    # ---- A, B alone; C, D together ------------------------------------
    for name in ("A", "B"):
        sets[name] = []
        for L in lengths:
            ans = submit(core, f"{name}{L}", docs[L] + ids(a.question), n)
            drain(core, [ans], n)
            sets[name].append(ans)
    sets["C"] = [submit(core, f"C{L}", docs[L] + ids(a.question), n)
                 for L in lengths]
    drain(core, sets["C"], n)
    sets["D"] = [submit(core, f"D{L}", ids(L), n) for L in lengths]
    drain(core, sets["D"], n)
    # Every run's margins are printed; what must pass is each SET (its four
    # lengths: 640 pairs) and each LENGTH (its four runs: 640 pairs).  The
    # rule is a share rule (a near-tie in the router gives a token another
    # expert in bf16 than in float32), and of a single run's 160 pairs it
    # allows one such position where the cell's check, 1,280 pairs, allows 12.
    found, runs = {}, []
    for name, answers in sets.items():
        for L, ans in zip(lengths, answers):
            found[name, L] = deltas(ans, reference(ans))
            whole = (len(docs[L]) // config["serve"]["block_size"]
                     * config["serve"]["block_size"])
            hit_ok = ans["cached"] == (whole if name in "BC" else 0)
            runs.append({"set": name, "length": L, "cached": ans["cached"],
                         "hit_ok": hit_ok,
                         **check.verdict(found[name, L], rule)})
            ok &= hit_ok
            note(f"{name} L={L}: {json.dumps(runs[-1])}")
    pooled = {
        **{f"set {name}": [d for L in lengths for d in found[name, L]]
           for name in sets},
        **{f"length {L}": [d for name in sets for d in found[name, L]]
           for L in lengths}}
    results = {key: check.verdict(ds, rule) for key, ds in pooled.items()}
    for key, v in results.items():
        ok &= v["ok"]
        note(f"{key}: {json.dumps(v)}")
    # ---- negative controls, against B's answers ------------------------
    controls = {}
    variants = {k: (run, t) for k, t in control_tables(ref, config).items()}
    variants["kv_one_precision_down"] = (run_fp8, tables)
    for cname, (fn, tabs) in variants.items():
        by_length = {L: deltas(ans, reference(ans, fn, tabs))
                     for L, ans in zip(lengths, sets["B"])}
        rows = {f"length {L}": check.verdict(ds, rule)
                for L, ds in by_length.items()}
        rows["set B"] = check.verdict(
            [d for ds in by_length.values() for d in ds], rule)
        controls[cname] = {k: {**v, "rejected": not v["ok"]}
                           for k, v in rows.items()}
        ok &= not any(v["ok"] for v in rows.values())
        note(f"control {cname}: {json.dumps(controls[cname])}")
    m = core.metrics()
    print(json.dumps({
        "ok": bool(ok), "device": jax.devices()[0].device_kind,
        "lengths": lengths, "rule": rule, "runs": runs, "pooled": results,
        "controls": controls,
        "prefill_programs_total": m["prefill_programs_total"],
        "window_walked_pct": 100.0 * m["decode_kv_window_blocks_walked_total"]
        / max(1, m["decode_kv_window_blocks_span_total"])}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
