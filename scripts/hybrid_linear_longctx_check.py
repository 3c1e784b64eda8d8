"""The agreement of a served recurrent-state configuration (``--config``:
solar-open2-ep16, the delta rule, by default; granite-4.0-h-small-ep2, the
state-space recurrence; jamba2-3b, the selective scan; ling-3.0-flash-ep4, the
delta rule's lower-bound gate beside a latent cache) with its reference over
a long answer, which the benchmark's ``correct`` cannot reach: it sees 8
greedy tokens behind at most 700, and the question a recurrent state raises is
what a thousand updates do to it.

    chiprun --timeout 1800 -- python3 scripts/hybrid_linear_longctx_check.py \
        [--config NAME] [--prompt 2048] [--answer 1024] [--seed N] [--tiny]
    ... scripts/hybrid_linear_longctx_check.py [--config NAME] \
        --check-seeds a,b,c [--bf16-state | --cache-one-precision-down]

(The second form runs the benchmark's own check alone, once a seed, and prints
its margins: how the configuration's ``check`` rule was set, and its negative
controls.  ``--bf16-state``: the state stored in bf16 between dispatches,
float32's next precision down — which the rule **cannot** tell from float32
at these widths: 6 linear layers' bf16 rows round more than a bf16 state
does (PERF.md section 6, PR 48).  ``--cache-one-precision-down``: everything
a sequence keeps between dispatches one precision below what the
configuration states — the state in bf16, the K/V rows (or the latent rows)
and the convolution's tail in float8 (e4m3) — which the rule rejects; for
ling-3.0-flash-ep4 only over the long answer of the first form, not over the
check's 8 tokens: PERF.md section 6, PR 66, and scripts/
ling_router_witness.py.)

One process, on the chip (``--tiny``: a toy size on the CPU, to rehearse the
script).  It builds cellbench/configs/<config>.json at its published
widths with seeded weights and serves it through ``EngineCore`` with the
cell's ``serve`` block: one request of ``--prompt`` tokens (four chunks of
512: the state crosses three chunk boundaries) and ``--answer`` greedy tokens,
each a decode step that updates every recurrent layer's state (6 x 64 heads;
9 x 128).  Against the configuration's own reference (cellbench/reference/
hybrid_linear.py, granite_hybrid.py) run over the whole sequence (the
recurrence one token at a time):

  (i)   the top-20 log-probabilities the engine returned for every generated
        position against the reference teacher-forced on those tokens, under
        the configuration's own ``check`` rule, over all positions and by
        eighths of the answer — does a float32 state drift?
  (ii)  the same engine with ``state`` stored in bf16 (models/
        hybrid_linear.py ``state_dtype``): reported, not held to anything —
        does a thousand roundings of the state show where eight tokens' do
        not?
  (iii) the negative control: the cache one precision down (above), which
        must fail that rule.

The last line is one JSON object with every margin; exit code 0 when (i)
passes in every eighth and (iii) fails.
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
    ask, logprob_verdict, note, serve)

TINY = dict(
    model_type="solar_open2", vocab_size=512, hidden_size=64,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    gqa_interval=3, gqa_layers=[0, 4], attention_layers=2, use_rope=False,
    use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
    first_k_dense_replace=0, tie_word_embeddings=False, n_routed_experts=2,
    n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    routed_scaling_factor=1, rms_norm_eps=1e-5, max_position_embeddings=4096,
    expert_parallel={"chips": 4, "router_experts": 8, "first_expert": 2},
    dtype="float32", reference="hybrid_linear",
    model_class="dynamo_tpu.models.hybrid_linear:HybridLinearModel",
    config_class="dynamo_tpu.models.hybrid_linear:HybridLinearConfig",
    serve={"max_batch_size": 4, "block_size": 16, "max_model_len": 1024,
           "prefill_chunk_tokens": 64, "num_blocks": 256},
    check={"abs_tol": 0.06, "share_within": 0.98, "median_tol": 0.002})

TINY_GRANITE = dict(
    model_type="granitemoehybrid", vocab_size=512, hidden_size=64,
    num_hidden_layers=6, attention_layers=1,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    attention_bias=False, attention_multiplier=0.1,
    position_embedding_type="nope", mamba_n_heads=4, mamba_d_head=32,
    mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    mamba_chunk_size=32, mamba_conv_bias=True, mamba_proj_bias=False,
    intermediate_size=32, shared_intermediate_size=48, num_local_experts=4,
    num_experts_per_tok=3, embedding_multiplier=12, residual_multiplier=0.22,
    logits_scaling=16, tie_word_embeddings=True, rms_norm_eps=1e-5,
    max_position_embeddings=4096,
    expert_parallel={"chips": 2, "router_experts": 8, "first_expert": 4},
    dtype="float32", reference="granite_hybrid",
    model_class=TINY["model_class"], config_class=TINY["config_class"],
    serve=TINY["serve"],
    # logits over logits_scaling 16: the float32 toy reads 0 to the last bit
    # and its cache one precision down 2.7e-4
    check={"abs_tol": 0.06, "share_within": 0.98, "median_tol": 1e-4})

TINY_JAMBA = dict(
    model_type="jamba", vocab_size=512, hidden_size=64, intermediate_size=96,
    num_hidden_layers=6, attention_layers=1, attn_layer_period=6,
    attn_layer_offset=2, expert_layer_period=2, expert_layer_offset=1,
    num_experts=1, num_experts_per_tok=1, num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, mamba_conv_bias=True,
    mamba_proj_bias=False, sliding_window=None, tie_word_embeddings=True,
    rms_norm_eps=1e-6, max_position_embeddings=4096,
    dtype="float32", reference="jamba_hybrid",
    model_class=TINY["model_class"], config_class=TINY["config_class"],
    serve=TINY["serve"],
    check={"abs_tol": 0.06, "share_within": 1.0, "median_tol": 1e-4})

TINY_LING = dict(
    model_type="ling_hybrid_mla", vocab_size=512, hidden_size=64,
    num_hidden_layers=4, published_layers=[0, 3, 4, 5], attention_layers=1,
    layer_group_size=3, first_k_dense_replace=1, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=6000000,
    partial_rotary_factor=0.5, rotary_dim=8, use_mla_nope=False,
    num_kv_heads_for_linear_attn=0, group_norm_size=1, linear_silu=True,
    short_conv_kernel_size=4, use_qk_norm=True, no_kda_lora=True,
    use_kda_lora=False, kda_safe_gate=True, kda_lower_bound=-5,
    gated_attention_proj_granularity_type="head_wise", num_experts=4,
    num_experts_per_tok=2, n_group=4, topk_group=2, score_function="sigmoid",
    moe_router_enable_expert_bias=True, norm_topk_prob=True,
    routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    max_position_embeddings=4096, tie_word_embeddings=False,
    expert_parallel={"chips": 4, "router_experts": 16, "first_expert": 4},
    dtype="float32", reference="ling_hybrid_mla",
    model_class=TINY["model_class"], config_class=TINY["config_class"],
    serve=TINY["serve"],
    # the XLA form of dense latent attention rounds its queries and
    # probabilities to bf16 whatever the cache holds: the float32 toy reads
    # a median of 8e-5, its bf16 state 1e-2, its cache one precision down 0.13
    check={"abs_tol": 0.06, "share_within": 0.98, "median_tol": 0.001})

# --config: the benchmark's file and the toy that rehearses it
CONFIGS = {"solar-open2-ep16": TINY, "granite-4.0-h-small-ep2": TINY_GRANITE,
           "jamba2-3b": TINY_JAMBA, "ling-3.0-flash-ep4": TINY_LING}


def bf16_state() -> None:
    """The negative control: every model built from here on stores its
    recurrent state in bf16, the nearest precision below the float32 the
    configuration states.  The arithmetic and the reference are not
    touched."""
    import jax.numpy as jnp

    from dynamo_tpu.models import hybrid_linear

    init = hybrid_linear.HybridLinearModel.__init__
    hybrid_linear.HybridLinearModel.__init__ = (
        lambda self, config, state_dtype=jnp.bfloat16:
        init(self, config, state_dtype))


def cache_one_precision_down() -> None:
    """The negative control of the check's rule: what a sequence keeps
    between dispatches, each in the nearest precision below the one the
    configuration states — the state in bf16 (``bf16_state``), the K/V rows
    (the latent rows of a model that keeps those) and the convolution's tail
    rounded to float8 (e4m3) before they are kept.  The arithmetic and the
    reference are not touched."""
    import jax

    from dynamo_tpu.models import hybrid_linear
    from dynamo_tpu.ops import latent_cache, linear_state

    bf16_state()
    # float8 e4m3's 4 exponent and 3 mantissa bits, as an operation of its
    # own: a cast there and back is a pair of converts, which the TPU
    # compiler is allowed to drop (excess precision) — and did
    f8 = lambda x: jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    write = hybrid_linear.write_kv_cache_layer
    hybrid_linear.write_kv_cache_layer = (
        lambda cache, layer, k, v, *rest, **kw:
        write(cache, layer, f8(k), f8(v), *rest, **kw))
    write_dense = latent_cache.write_dense
    latent_cache.write_dense = (
        lambda latent, layer, rows, slots:
        write_dense(latent, layer, f8(rows), slots))
    conv = linear_state.short_conv

    def short_conv(x, w, tail, n_real, bias=None):
        y, new = conv(x, w, tail, n_real, bias)
        return y, f8(new)

    linear_state.short_conv = short_conv


# tolerances at which --check-seeds also prints the share of pairs within
# (the small ones for a model whose logits are divided by logits_scaling)
SHARES_AT = (0.003, 0.004, 0.005, 0.006, 0.008, 0.01, 0.015,
             0.15, 0.2, 0.25, 0.3, 0.35, 0.4, 0.5, 0.6, 0.8)

CONTROLS = {"bf16_state": bf16_state,
            "cache_one_precision_down": cache_one_precision_down}


def long_answer(core, run, config: dict, a, name: str) -> dict:
    """One request of a.prompt + a.answer tokens through ``core``; its
    margins against the reference over the whole answer and by eighths."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(a.seed)
    prompt = rng.integers(1, config["vocab_size"], a.prompt).tolist()
    ans = ask(core, name, prompt, a.answer)
    seq = prompt + ans["tokens"]
    rows = list(range(a.prompt - 1, a.prompt - 1 + a.answer))
    padded = np.zeros(-(-len(seq) // 128) * 128, np.int32)
    padded[:len(seq)] = seq
    logp = np.asarray(run(core.params, jnp.asarray(padded), jnp.asarray(rows)))
    out = {"all": logprob_verdict(ans["top"], logp, config["check"])}
    step = max(1, a.answer // 8)
    out["by_position"] = [
        {"from": i, **logprob_verdict(ans["top"][i:i + step],
                                      logp[i:i + step], config["check"])}
        for i in range(0, a.answer, step)]
    m = core.metrics()
    out["state_position_mismatches_total"] = m["state_position_mismatches_total"]
    out["prefill_dispatches"] = m["prefill_dispatches_total"]
    note(f"{name}: all {json.dumps(out['all'])}")
    for part in out["by_position"]:
        note(f"{name}: from {part['from']:5d}: median {part['median']:.4f} "
             f"share {part['share_within']:.4f} max {part['max']:.3f} "
             f"ok {part['ok']}")
    return out


async def check_margins(config: dict, seeds: list[int]) -> list[dict]:
    """cellbench's own ``correct`` (check.run: prompts of 17-700 tokens over
    HTTP, alone and together, top-20 log-probabilities against the float32
    reference) for each seed, with its margins."""
    import tempfile

    from cellbench import check, server, spec

    out = []
    settings = spec.load_settings(ROOT)
    gen = spec.load_module(ROOT, "generators", "mix_fixed_order")
    rule = check.verdict

    def verdict(all_deltas, limits):
        """The rule's verdict and, for choosing ``abs_tol``, the share of
        pairs within other tolerances."""
        return {**rule(all_deltas, limits), "within": {
            str(tol): sum(d <= tol for d in all_deltas) / len(all_deltas)
            for tol in SHARES_AT}}

    check.verdict = verdict
    for seed in seeds:
        with tempfile.TemporaryDirectory() as work:
            served = await server.start(config, seed, work)
            try:
                v = await check.run(served, config, settings, seed, ROOT, gen)
                v["state_position_mismatches_total"] = served.core.metrics()[
                    "state_position_mismatches_total"]
            finally:
                await served.stop()
        note(f"check seed {seed}: {json.dumps(v)}")
        out.append({"seed": seed, **v})
    return out


def a_process_a_seed(script: str, seeds: str, control, flags: list) -> int:
    """``script --check-seeds <seed> <flags>`` once a seed of the comma
    separated ``seeds``, each in a process of its own: a served model's
    arrays outlive its engine, and two do not fit the chip (the caller has
    not touched jax yet).  Prints every seed's margins and one JSON line of
    them all."""
    import subprocess

    rows = []
    for seed in seeds.split(","):
        out = subprocess.run(
            [sys.executable, script, "--check-seeds", seed, *flags],
            stdin=subprocess.DEVNULL, capture_output=True, text=True)
        last = [l for l in out.stdout.splitlines() if l.startswith("{")]
        if out.returncode or not last:
            print(out.stdout[-2000:], out.stderr[-2000:], flush=True)
            return 1
        rows += json.loads(last[-1])["checks"]
        note(f"check seed {seed}: {json.dumps(rows[-1])}")
    print(json.dumps({"control": control, "checks": rows}), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="solar-open2-ep16",
                   choices=sorted(CONFIGS))
    p.add_argument("--check-seeds", default=None,
                   help="run only the benchmark's own check, once a seed "
                        "(comma separated), and print its margins")
    p.add_argument("--bf16-state", action="store_true",
                   help="with --check-seeds: the state stored in bf16 "
                        "between dispatches")
    p.add_argument("--cache-one-precision-down", action="store_true",
                   help="with --check-seeds: the negative control, state "
                        "bf16 + K/V rows and convolution tail float8")
    p.add_argument("--prompt", type=int, default=2048)
    p.add_argument("--answer", type=int, default=1024)
    p.add_argument("--seed", type=int, default=2**31 + 41)
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--tiny", action="store_true",
                   help="rehearse at a toy size on the CPU")
    a = p.parse_args(argv)
    control = ("cache_one_precision_down" if a.cache_one_precision_down
               else "bf16_state" if a.bf16_state else None)
    if a.check_seeds and "," in a.check_seeds:
        return a_process_a_seed(
            __file__, a.check_seeds, control,
            ["--config", a.config]
            + (["--bf16-state"] if a.bf16_state else [])
            + (["--cache-one-precision-down"]
               if a.cache_one_precision_down else [])
            + (["--tiny"] if a.tiny else []))
    import jax

    from cellbench import spec

    if a.tiny:
        config = CONFIGS[a.config]
        a.prompt, a.answer = 200, 96
        a.num_blocks = config["serve"]["num_blocks"]
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(
            ROOT / "cellbench/configs" / f"{a.config}.json")
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    note(f"compile cache: {enable_persistent_cache()}")
    if a.check_seeds:
        import asyncio

        if control:
            CONTROLS[control]()
        rows = asyncio.run(check_margins(
            config, [int(x) for x in a.check_seeds.split(",")]))
        print(json.dumps({"control": control, "checks": rows}), flush=True)
        return 0
    from cellbench import server
    from dynamo_tpu.engine import EngineCore

    model, core = serve(config, a.seed, a.num_blocks)
    ref = spec.load_module(ROOT, "reference", config["reference"])
    run = jax.jit(ref.make_forward(config))
    real = long_answer(core, run, config, a, "float32-state")
    # a control takes the engine's place: two states do not fit the chip
    params, ecfg = core.params, core.config
    del core
    controls = {}
    for name, damage in CONTROLS.items():
        damage()
        damaged = server.resolve(config["model_class"])(
            server.model_config(config))
        controls[name] = long_answer(
            EngineCore(damaged, params, ecfg, eos_token_ids=[]), run,
            config, a, name)
    ok = (all(part["ok"] for part in real["by_position"]) and real["all"]["ok"]
          and real["state_position_mismatches_total"] == 0
          and not controls["cache_one_precision_down"]["all"]["ok"])
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind,
                      "prompt": a.prompt, "answer": a.answer,
                      "float32_state": real, **controls}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
