"""The agreement of the served GLM-5.2 configuration with its reference at the
context lengths the cell times, which the benchmark's ``correct`` cannot
reach (its prompts are at most 708 tokens, under ``index_topk``: the indexer
runs there, the selection never binds).

    chiprun --timeout 3000 -- python3 scripts/glm_longctx_check.py \
        [--contexts 4096,16384,32768] [--seed N] [--tiny]
    ... scripts/glm_longctx_check.py --check-seeds a,b,c [--fp8-latent]

(The second form runs the benchmark's own check alone, once a seed, and prints
its margins: how the configuration's ``check`` rule was set, and its negative
control.)

One process, on the chip (``--tiny``: a toy size on the CPU, to rehearse the
script).  It builds the configuration of cellbench/configs/glm-5.2-ep16.json
at its published widths with seeded weights, serves it through ``EngineCore``
with the cell's ``serve`` block (a smaller pool, to leave the float32
reference room), and for each context length L:

  A  a document of L tokens + a 256-token question, 64 greedy tokens: chunked
     prefill and the question chunk (the masked form: every chunk of one
     sequence under ~60 k positions since PR 65), the decode steps (the
     gather kernel);
  B  the same document + another question: a prefix hit of L tokens, the
     question by the masked kernel over that past with its true lengths (a
     bucket of 256 tokens and a power-of-two prefix, of which the question
     and L + 256 positions exist), the decode steps.

(64 tokens a run and not 8: the configuration's ``check`` rule is a share,
because where the router's near tie picks another expert in bf16 than in
float32, and that expert is held here, a position is off by more than
rounding.  The benchmark's check applies it to 1,280 pairs; at 160 one such
position is an eighth of the sample — with 8 tokens one run of six read
0.981 within 0.5 on the chip, with 64 all six 0.995-1.0: PERF.md, PR 37.)

Then, against cellbench/reference/glm_dsa.py run over B's whole sequence:

  (i)   at 16 of the question's positions and the decode positions, in both
        ``full`` layers: the program's index scores of the positions it
        selected lie within ``--score-tol`` (in standard deviations of the
        query's own scores) of the reference's, and every
        position on which the two selections disagree has a reference score
        within that tolerance of the reference's 2,048th (with seeded
        weights the boundary is dense: equal sets are not the test);
  (ii)  the top-20 log-probabilities the engine returned for A's and B's
        tokens, against the reference teacher-forced on those tokens and — at
        the rows compared — on the program's own selected sets, under the
        configuration's ``check`` rule;
  (iii) two negative controls, which must each fail (i) or (ii): the program
        with ``index_topk`` 1,024 in place of 2,048, and the program with its
        ``shared`` layers given random positions of their own.

The last line is one JSON object with every margin; exit code 0 when (i) and
(ii) pass and both controls fail.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

T0 = time.monotonic()


def note(msg: str) -> None:
    print(f"# [{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


TINY = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=32, q_lora_rank=48, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=2, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=2.5, norm_topk_prob=True,
    scoring_func="sigmoid", topk_method="noaux_tc", index_n_heads=8,
    index_head_dim=16, index_topk=64,
    indexer_types=["full", "full", "shared", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
    first_k_dense_replace=1, rms_norm_eps=1e-5, max_position_embeddings=4096,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    expert_parallel={"router_experts": 8, "first_expert": 2},
    dtype="float32", reference="glm_dsa",
    model_class="dynamo_tpu.models.glm_dsa:GlmDsaModel",
    config_class="dynamo_tpu.models.glm_dsa:GlmDsaConfig",
    serve={"max_batch_size": 4, "block_size": 16, "max_model_len": 1024,
           "prefill_chunk_tokens": 128, "num_blocks": 256},
    check={"abs_tol": 0.25, "share_within": 0.9, "median_tol": 0.05})


def serve(config: dict, seed: int, num_blocks: int):
    """(model, core): the configuration served as cellbench/server.py serves
    it — seeded weights made on the device, EngineCore with the serve block."""
    import jax

    from cellbench import server
    from dynamo_tpu.engine import EngineCore

    model = server.resolve(config["model_class"])(server.model_config(config))
    params = server.make_params(model, seed, None)
    jax.block_until_ready(params)
    ecfg = server.engine_config(server.run_args(
        {**config["serve"], "num_blocks": num_blocks}))
    return model, EngineCore(model, params, ecfg, eos_token_ids=[])


def ask(core, name: str, prompt: list[int], n: int) -> dict:
    """One greedy request through the engine: tokens, top-20 candidates per
    token, the blocks it held and how much of its prompt was cached."""
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    out = {"tokens": [], "top": [], "blocks": None, "cached": None}

    def emit(o):
        out["tokens"] += list(o.token_ids)
        out["top"] += [dict(pairs) for pairs in (o.top_logprobs or [])]
        if out["req"].block_ids:        # while it still holds them
            out["blocks"] = list(out["req"].block_ids)

    def allocated(req):
        out["req"] = req

    core.submit(EngineRequest(
        request_id=name, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0, logprobs=True,
                                 top_logprobs=20),
        stops=StopConditions(max_tokens=n, ignore_eos=True),
        emit=emit, on_allocated=allocated))
    while core.step():
        pass
    out["cached"] = out.pop("req").cached_tokens
    assert len(out["tokens"]) == n and len(out["top"]) == n, (
        name, len(out["tokens"]), len(out["top"]))
    return out


def probe(model, core, seq, blocks, rows):
    """What each ``full`` layer's indexer selects for the tokens at ``rows``
    of ``seq`` against the cache as the engine left it (nothing is written):
    per full layer (positions [n, K], scores [n, K], nvalid [n]), and the
    log-probabilities after those tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    m = core.config.max_blocks_per_seq
    bt = np.zeros((1, m), np.int32)
    bt[0, :len(blocks)] = blocks
    rows = np.asarray(rows, np.int32)
    # one row a query: each sees the cache up to its own position only
    n = len(rows)
    fwd = jax.jit(lambda p, c, t, pos, b, l: model.forward(
        p, t, pos, c, b, l, jnp.full(t.shape, -1, jnp.int32), probe=True))
    hidden, _, seen = fwd(
        core.params, core.cache, jnp.asarray(np.asarray(seq)[rows][:, None]),
        jnp.asarray(rows[:, None]), jnp.asarray(np.repeat(bt, n, axis=0)),
        jnp.asarray(rows + 1))
    logp = jax.nn.log_softmax(
        model.compute_logits(core.params, hidden[:, 0]), axis=-1)
    return [tuple(np.asarray(a) for a in layer) for layer in seen], np.asarray(logp)


def bits_of(positions, nvalid, t: int):
    """Selected positions [n, K] -> one bit a (row, key) pair, [n, t/32]."""
    import numpy as np

    mask = np.zeros((len(positions), t), bool)
    for i, (pos, nv) in enumerate(zip(positions, nvalid)):
        mask[i, pos[:nv]] = True
    words = mask.reshape(len(positions), t // 32, 32).astype(np.uint32)
    return (words << np.arange(32, dtype=np.uint32)).sum(axis=-1, dtype=np.uint32)


def compare_selection(seen, ref_scores, rows, topk: int, tol: float) -> dict:
    """(i) for every full layer: program scores against the reference's at
    the positions the program selected, and the boundary test on the
    positions where the two selections differ."""
    import numpy as np

    worst_score = worst_edge = 0.0
    differ = total = 0
    spreads, by_layer = [], []
    for layer, (pos, vals, nvalid) in enumerate(seen):
        by_layer.append(0.0)
        for i, row in enumerate(rows):
            nv = int(nvalid[i])
            ref = ref_scores[layer, i, :row + 1]
            # differences are counted in standard deviations of the query's
            # own scores: their scale is the weights', not a unit
            spread = float(ref.std()) or 1.0
            spreads.append(spread)
            ref = ref / spread
            off = float(np.abs(vals[i, :nv] / spread - ref[pos[i, :nv]]).max())
            by_layer[-1] = max(by_layer[-1], off)
            worst_score = max(worst_score, off)
            k = min(topk, row + 1)
            order = np.argsort(-ref, kind="stable")[:k]
            mine, theirs = set(pos[i, :nv].tolist()), set(order.tolist())
            odd = np.asarray(sorted(mine ^ theirs), np.int64)
            total += k
            differ += len(odd) // 2
            if len(odd):
                worst_edge = max(worst_edge, float(
                    np.abs(ref[odd] - ref[order[-1]]).max()))
    return {"ok": worst_score <= tol and worst_edge <= tol,
            "score_max_diff_sd": worst_score,
            "score_max_diff_sd_by_full_layer": by_layer,
            "boundary_max_distance_sd": worst_edge,
            "score_sd_median": float(np.median(spreads)),
            "positions_differing": differ, "positions_selected": total}


def top20(logp) -> list[dict]:
    """The 20 likeliest tokens of each row of log-probabilities, as the
    engine reports them: {token id: log-probability}."""
    import numpy as np

    return [dict(zip(np.argsort(-row)[:20].tolist(),
                     np.sort(row)[::-1][:20].tolist())) for row in logp]


def logprob_verdict(answers, ref_logp, rule: dict) -> dict:
    """(ii): the engine's top-20 log-probabilities against the reference's
    at the same positions, under the configuration's check rule."""
    from cellbench import check

    deltas = []
    for top, ref in zip(answers, ref_logp):
        deltas += [abs(lp - float(ref[tid])) for tid, lp in top.items()]
    return check.verdict(deltas, rule)


def one_context(model, core, ref, config, length, seed, a) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed * 1000 + length)
    vocab = config["vocab_size"]
    doc = rng.integers(1, vocab, length).tolist()
    q1, q2 = (rng.integers(1, vocab, a.question).tolist() for _ in range(2))
    topk = config["index_topk"]
    out = {"context": length}
    run = jax.jit(ref.make_probe(config))
    keep = None
    # A is compared before B is served: B may take the blocks A freed
    for name, question in (("A", q1), ("B", q2)):
        computed = core.prompt_tokens_computed
        ans = ask(core, f"{name}{length}", doc + question, a.answer)
        if name == "B":
            out["prefix_hit"] = {
                "cached_tokens": ans["cached"],
                "computed": core.prompt_tokens_computed - computed}
        seq = doc + question + ans["tokens"]
        n_prompt = length + a.question
        # rows compared: 16 spread over the question, and the positions
        # whose next-token distributions the engine returned
        q_rows = np.linspace(length, n_prompt - 2, 16).astype(int).tolist()
        d_rows = list(range(n_prompt - 1, n_prompt - 1 + a.answer))
        rows = q_rows + d_rows
        padded = np.zeros(-(-len(seq) // 128) * 128, np.int32)
        padded[:len(seq)] = seq
        seen, _ = probe(model, core, seq, ans["blocks"], rows)
        _, scores, own = run(core.params, jnp.asarray(padded),
                             jnp.asarray(rows))
        # teacher-force the rows compared on the program's own selections
        forced = np.asarray(own).copy()
        for layer, (pos, _, nvalid) in enumerate(seen):
            forced[layer, rows] = bits_of(pos, nvalid, len(padded))
        logp, _, _ = run(core.params, jnp.asarray(padded), jnp.asarray(rows),
                         jnp.asarray(forced))
        sel = compare_selection(seen, np.asarray(scores), rows, topk,
                                a.score_tol)
        lp = logprob_verdict(ans["top"], np.asarray(logp)[len(q_rows):],
                             config["check"])
        out[name] = {"selection": sel, "logprobs": lp}
        note(f"L={length} {name}: cached {ans['cached']}; (i) "
             f"{json.dumps(sel)} (ii) {json.dumps(lp)}")
        keep = (seq, ans, rows, q_rows, np.asarray(scores), np.asarray(logp))
    # ---- negative controls, on B's decode rows ----
    seq, ans, rows, q_rows, scores, logp = keep
    d_rows = rows[len(q_rows):]
    from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel

    half = GlmDsaModel(GlmDsaConfig.from_hf_config(
        {**config, "index_topk": topk // 2},
        dtype=config.get("dtype", "bfloat16")))
    seen_half, logp_half = probe(half, core, seq, ans["blocks"], d_rows)
    ctl = {"half_topk": {
        "selection": compare_selection(
            seen_half, scores[:, len(q_rows):], d_rows, topk, a.score_tol),
        "logprobs": logprob_verdict(
            top20(logp_half), logp[len(q_rows):], config["check"])}}

    class Unshared(GlmDsaModel):
        """``shared`` layers attend to random positions of their own."""

        def _attention(self, lp, li, fi, h_in, positions, cache,
                       block_tables, seq_lens, slot_idx, sel, ctx_blocks,
                       sparse, full, groups=None):
            if not full:
                slots, nvalid = sel[:2]
                key = jax.random.fold_in(jax.random.PRNGKey(0), li)
                pos = jax.random.randint(
                    key, slots.shape, 0, jnp.maximum(positions.reshape(-1, 1), 1))
                bs = cache["latent"].shape[2]
                b, s = positions.shape
                pos = pos.reshape(b, -1)
                rnd = (jnp.take_along_axis(block_tables, pos // bs, axis=1)
                       * bs + pos % bs).reshape(slots.shape)
                sel = (rnd, nvalid, *sel[2:])
            return super()._attention(
                lp, li, fi, h_in, positions, cache, block_tables, seq_lens,
                slot_idx, sel, ctx_blocks, sparse, full, groups)

    _, logp_rnd = probe(Unshared(model.config), core, seq, ans["blocks"], d_rows)
    ctl["unshared_random"] = {"logprobs": logprob_verdict(
        top20(logp_rnd), logp[len(q_rows):], config["check"])}
    for c in ctl.values():
        c["rejected"] = not all(v["ok"] for v in c.values() if isinstance(v, dict))
    out["controls"] = ctl
    note(f"L={length} controls: {json.dumps(ctl)}")
    out["ok"] = (all(out[n][k]["ok"] for n in ("A", "B")
                     for k in ("selection", "logprobs"))
                 and out["prefix_hit"]["cached_tokens"] == length
                 and all(c["rejected"] for c in ctl.values()))
    return out


def fp8_latent_rows() -> None:
    """The negative control of the check's rule: the latent rows are rounded
    to float8 (e4m3), the nearest precision below the bf16 the configuration
    states, before they are packed into the cache.  The reference is not
    touched."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import latent_cache

    pack = latent_cache.pack_rows
    latent_cache.pack_rows = lambda rows: pack(
        rows.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16))


async def check_margins(config: dict, seeds: list[int]) -> list[dict]:
    """cellbench's own ``correct`` (check.run: prompts of 17-700 tokens over
    HTTP, alone and together, top-20 log-probabilities against the float32
    reference) for each seed, with its margins."""
    import tempfile

    from cellbench import check, server, spec

    out = []
    settings = spec.load_settings(ROOT)
    gen = spec.load_module(ROOT, "generators", "shared_docs")
    for seed in seeds:
        with tempfile.TemporaryDirectory() as work:
            served = await server.start(config, seed, work)
            try:
                v = await check.run(served, config, settings, seed, ROOT, gen)
            finally:
                await served.stop()
        note(f"check seed {seed}: {json.dumps(v)}")
        out.append({"seed": seed, **v})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--check-seeds", default=None,
                   help="run only the benchmark's own check, once a seed "
                        "(comma separated), and print its margins")
    p.add_argument("--fp8-latent", action="store_true",
                   help="with --check-seeds: the negative control, latent "
                        "rows rounded to float8 before they are cached")
    p.add_argument("--contexts", default="4096,16384,32768")
    p.add_argument("--seed", type=int, default=2**31 + 37)
    p.add_argument("--question", type=int, default=256)
    p.add_argument("--answer", type=int, default=64,
                   help="tokens generated a run: x 20 log-probabilities = "
                        "the pairs its rule is held to (the benchmark's check "
                        "has 1,280)")
    p.add_argument("--num-blocks", type=int, default=2400,
                   help="cache blocks of the pool (the cell's 14,400 leave "
                        "the float32 reference no room at 33 k tokens)")
    p.add_argument("--score-tol", type=float, default=2.0,
                   help="|program - reference| an index score may differ by, "
                        "in standard deviations of the query's scores (bf16 "
                        "against float32 read 0.6-1.1 on the chip, the two "
                        "negative controls 3.9-6.9: PERF.md, PR 37)")
    p.add_argument("--tiny", action="store_true",
                   help="rehearse at a toy size on the CPU")
    a = p.parse_args(argv)
    if a.check_seeds and "," in a.check_seeds:
        # one process a seed: a served model's arrays outlive its engine, and
        # two do not fit the chip (this process has not touched jax yet)
        import subprocess

        rows = []
        for seed in a.check_seeds.split(","):
            out = subprocess.run(
                [sys.executable, __file__, "--check-seeds", seed]
                + (["--fp8-latent"] if a.fp8_latent else [])
                + (["--tiny"] if a.tiny else []),
                stdin=subprocess.DEVNULL, capture_output=True, text=True)
            last = [l for l in out.stdout.splitlines() if l.startswith("{")]
            if out.returncode or not last:
                print(out.stdout[-2000:], out.stderr[-2000:], flush=True)
                return 1
            rows += json.loads(last[-1])["checks"]
            note(f"check seed {seed}: {json.dumps(rows[-1])}")
        print(json.dumps({"fp8_latent": a.fp8_latent, "checks": rows}),
              flush=True)
        return 0
    import jax

    from cellbench import spec

    if a.tiny:
        config = TINY
        contexts = [256, 512]
        a.question, a.num_blocks = 32, TINY["serve"]["num_blocks"]
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(ROOT / "cellbench/configs/glm-5.2-ep16.json")
        contexts = [int(c) for c in a.contexts.split(",")]
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    note(f"compile cache: {enable_persistent_cache()}")
    if a.check_seeds:
        import asyncio

        if a.fp8_latent:
            fp8_latent_rows()
        rows = asyncio.run(check_margins(
            config, [int(x) for x in a.check_seeds.split(",")]))
        print(json.dumps({"fp8_latent": a.fp8_latent, "checks": rows}),
              flush=True)
        return 0
    model, core = serve(config, a.seed, a.num_blocks)
    note("attention: " + json.dumps(
        {k: v[0] for k, v in core.attention_impls().items()}))
    ref = spec.load_module(ROOT, "reference", config["reference"])
    results = [one_context(model, core, ref, config, n, a.seed, a)
               for n in contexts]
    ok = all(r["ok"] for r in results)
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind,
                      "score_tol": a.score_tol, "contexts": results}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
