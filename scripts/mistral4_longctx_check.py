"""The agreement of the served Mistral-Small-4 configuration with its reference
at the context lengths the cell times, which the benchmark's ``correct``
cannot reach: its prompts are at most 708 tokens, under the trained context of
8,192, so the position-dependent query scale is 1 there and YaRN's slow pairs
have hardly turned.

    chiprun --timeout 3000 -- python3 scripts/mistral4_longctx_check.py \
        [--contexts 4096,16384,32768] [--seed N] [--tiny]
    ... scripts/mistral4_longctx_check.py --check-seeds a,b,c [--fp8-latent]

(The second form runs the benchmark's own check alone, once a seed, and prints
its margins: how the configuration's ``check`` rule was set, and its negative
control — the latent rows rounded to float8 before they are cached.)

One process, on the chip (``--tiny``: a toy size on the CPU, to rehearse the
script).  It builds cellbench/configs/mistral-small-4-ep8.json at its
published widths with seeded weights, serves it through ``EngineCore`` with
the cell's ``serve`` block (a smaller pool, to leave the float32 reference
room), and for each context length L:

  A  a document of L tokens + a 256-token question, 64 greedy tokens: chunked
     prefill (``mla_dense_prefill`` over a growing prefix), the question
     chunk, the decode steps (``mla_dense_decode``);
  B  the same document + another question: a prefix hit of L tokens, the
     question over that past, the decode steps.

Then, against cellbench/reference/mistral4_mla.py run over each whole
sequence (queries in blocks, experts one at a time):

  (i)   the top-20 log-probabilities the engine returned for A's and B's 64
        tokens against the reference teacher-forced on those tokens, under
        the configuration's own ``check`` rule (1,280 pairs a run, the
        benchmark check's own number);
  (ii)  two negative controls on B's decode rows, which must each fail that
        rule: the program with the position's query scale left out (at
        L >= the trained context: below it the scale is 1 and there is
        nothing to leave out), and the program with plain RoPE frequencies
        in place of YaRN's.  A control runs the decode rows again, one query
        a row, over the cache the real program left.

The last line is one JSON object with every margin; exit code 0 when (i)
passes, every prefix hit was whole and the controls fail.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# serving through EngineCore, one greedy request with its top-20 candidates,
# and the comparison under a check rule are the GLM script's
from scripts.glm_longctx_check import (  # noqa: E402
    ask, logprob_verdict, note, serve, top20)

CONFIG = ROOT / "cellbench/configs/mistral-small-4-ep8.json"


TINY = dict(
    model_type="mistral4", vocab_size=512, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=32, q_lora_rank=48,
    intermediate_size=96, moe_intermediate_size=32, n_routed_experts=2,
    num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=1,
    norm_topk_prob=True, first_k_dense_replace=0, rms_norm_eps=1e-6,
    rope_interleave=True, max_position_embeddings=4096,
    rope_parameters={
        "beta_fast": 32, "beta_slow": 1, "factor": 8,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 128, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    expert_parallel={"chips": 4, "router_experts": 8, "first_expert": 2},
    dtype="float32", reference="mistral4_mla",
    model_class="dynamo_tpu.models.glm_dsa:GlmDsaModel",
    config_class="dynamo_tpu.models.glm_dsa:GlmDsaConfig",
    serve={"max_batch_size": 4, "block_size": 16, "max_model_len": 1024,
           "prefill_chunk_tokens": 128, "num_blocks": 256},
    check={"abs_tol": 0.25, "share_within": 0.95, "median_tol": 0.02})


def rerun_rows(model, core, seq, blocks, rows):
    """The log-probabilities after the tokens at ``rows`` of ``seq``, each
    computed as one query a row by ``model`` against the cache as the engine
    left it (nothing is written: every slot is -1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    m = core.config.max_blocks_per_seq
    bt = np.zeros((1, m), np.int32)
    bt[0, :len(blocks)] = blocks
    rows = np.asarray(rows, np.int32)
    n = len(rows)
    fwd = jax.jit(lambda p, c, t, pos, b, l: model.forward(
        p, t, pos, c, b, l, jnp.full(t.shape, -1, jnp.int32)))
    hidden, _ = fwd(
        core.params, core.cache, jnp.asarray(np.asarray(seq)[rows][:, None]),
        jnp.asarray(rows[:, None]), jnp.asarray(np.repeat(bt, n, axis=0)),
        jnp.asarray(rows + 1))
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(core.params, hidden[:, 0]), axis=-1))


def controls(model, config: dict) -> dict:
    """name -> the program with one piece of the mathematics left out."""
    import copy

    from dynamo_tpu.models.llama import rope_inv_freq

    no_scale = copy.copy(model)
    no_scale.config = copy.copy(model.config)
    no_scale.config.query_scale_beta = 0.0
    plain = copy.copy(model)
    plain.inv_freq = rope_inv_freq(model.config.qk_rope_head_dim,
                                   model.config.rope_theta)
    return {"no_query_scale": no_scale, "plain_rope": plain}


def one_context(model, core, ref, config, length, seed, a) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed * 1000 + length)
    vocab = config["vocab_size"]
    doc = rng.integers(1, vocab, length).tolist()
    q1, q2 = (rng.integers(1, vocab, a.question).tolist() for _ in range(2))
    trained = config["rope_parameters"]["original_max_position_embeddings"]
    out = {"context": length}
    run = jax.jit(ref.make_forward(config))
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
        # the positions whose next-token distributions the engine returned
        rows = list(range(n_prompt - 1, n_prompt - 1 + a.answer))
        padded = np.zeros(-(-len(seq) // 128) * 128, np.int32)
        padded[:len(seq)] = seq
        logp = np.asarray(run(core.params, jnp.asarray(padded),
                              jnp.asarray(rows)))
        out[name] = logprob_verdict(ans["top"], logp, config["check"])
        note(f"L={length} {name}: cached {ans['cached']}; "
             f"{json.dumps(out[name])}")
    # ---- negative controls, on B's decode rows (the cache is B's) ----
    ctl = {}
    for cname, damaged in controls(model, config).items():
        if cname == "no_query_scale" and length < trained:
            continue                   # the scale is 1: nothing is left out
        v = logprob_verdict(
            top20(rerun_rows(damaged, core, seq, ans["blocks"], rows)),
            logp, config["check"])
        ctl[cname] = {**v, "rejected": not v["ok"]}
    # the real program through the same re-run: the control's own control
    same = logprob_verdict(
        top20(rerun_rows(model, core, seq, ans["blocks"], rows)), logp,
        config["check"])
    out["rerun_undamaged"] = same
    out["controls"] = ctl
    note(f"L={length} rerun {json.dumps(same)} controls: {json.dumps(ctl)}")
    out["ok"] = (out["A"]["ok"] and out["B"]["ok"] and same["ok"]
                 and out["prefix_hit"]["cached_tokens"] == length
                 and all(c["rejected"] for c in ctl.values()))
    return out


def fp8_latent_rows() -> None:
    """The negative control of the check's rule: the latent rows are rounded
    to float8 (e4m3), the nearest precision below the bf16 the configuration
    states, before they are written to the cache.  The reference is not
    touched."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import latent_cache

    write = latent_cache.write_dense
    latent_cache.write_dense = lambda latent, layer, rows, slots: write(
        latent, layer, rows.astype(jnp.float8_e4m3fn).astype(rows.dtype),
        slots)


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
                m = served.core.metrics()
                v["held_pick_pct"] = 100.0 * m["moe_held_picks_total"] / max(
                    1, m["moe_router_picks_total"])
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
                        "the pairs the rule is held to (the benchmark's check "
                        "has 1,280)")
    p.add_argument("--num-blocks", type=int, default=2400,
                   help="cache blocks of the pool (the cell's 14,400 leave "
                        "the float32 reference no room at 33 k tokens)")
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
        contexts = [96, 256, 512]
        a.question, a.num_blocks = 32, TINY["serve"]["num_blocks"]
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(CONFIG)
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
                      "contexts": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
