"""The agreement of the served ``zaya1-8b`` configuration with its reference
over a long answer, which the benchmark's ``correct`` cannot reach (it sees 8
greedy tokens behind at most 700), and the margins its ``check`` rule was set
from.  The sibling of scripts/hybrid_linear_longctx_check.py for a model
whose per-slot state is a *tail* — the last inputs of two convolutions and of
the value shift — beside K/V rows in every layer: the question a thousand
decode steps raise is whether a tail read one step later is the row that was
written (a stale or shifted tail does not grow, it is wrong at once and stays
wrong), and whether 20 top-1 routers in a row drift apart from the
reference's.

    chiprun --timeout 1800 -- python3 scripts/zaya_longctx_check.py \
        [--prompt 2048] [--answer 1024] [--seed N] [--tiny]
    ... scripts/zaya_longctx_check.py --check-seeds a,b,c \
        [--cache-one-precision-down]

(The second form runs the benchmark's own check alone, once a seed, and
prints its margins.  ``--cache-one-precision-down`` is the negative control:
everything a sequence keeps between dispatches — its K/V rows and its tails —
rounded to float8 (e4m3), the nearest precision below the bf16 the
configuration states, before it is kept; arithmetic and reference untouched.)

One process, on the chip (``--tiny``: a toy size on the CPU, to rehearse the
script).  The first form serves the configuration through ``EngineCore`` with
the cell's ``serve`` block: one request of ``--prompt`` tokens (four chunks of
512: the tails cross three chunk boundaries) and ``--answer`` greedy tokens,
each a decode step that reads and rewrites every layer's tail.  Against the
reference run over the whole sequence: (i) the top-20 log-probabilities of
every generated position under the configuration's own ``check`` rule, over
all positions and by eighths of the answer; (ii) the same with the cache one
precision down, whose median must be ``CONTROL_RATIO`` times the served
one's (the rule's limits were set at the check's 17-700 tokens; over 3,072
both readings are lower).  The last line is one JSON object with every
margin; exit code 0 when (i) passes in every eighth and (ii) holds.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from scripts import hybrid_linear_longctx_check as recurrent  # noqa: E402
from scripts.glm_longctx_check import note, serve  # noqa: E402

CONFIG = "zaya1-8b"
TINY = dict(
    model_type="zaya", vocab_size=512, hidden_size=64, num_hidden_layers=4,
    layer_types=["hybrid"] * 4, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, attention_bias=False, lm_head_bias=False, cca_time0=2,
    cca_time1=2, hidden_act="silu", moe_intermediate_size=32, num_experts=4,
    num_experts_per_tok=1, router_hidden_size=32, partial_rotary_factor=0.5,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5,
                                "rope_theta": 5000000, "rope_type": "default"}},
    sliding_window=None, tie_word_embeddings=True, rms_norm_eps=1e-5,
    max_position_embeddings=4096, dtype="float32", reference="zaya_cca",
    model_class="dynamo_tpu.models.zaya:ZayaModel",
    config_class="dynamo_tpu.models.zaya:ZayaConfig",
    serve={"max_batch_size": 4, "block_size": 16, "max_model_len": 1024,
           "prefill_chunk_tokens": 64, "num_blocks": 256},
    # the float32 toy reads 1e-5; its cache one precision down 0.05
    check={"abs_tol": 0.005, "share_within": 0.98, "median_tol": 0.001})

# the long answer's control must read this many times the served median
CONTROL_RATIO = 3.0
# tolerances at which --check-seeds also prints the share of pairs within
recurrent.SHARES_AT = (0.005, 0.01, 0.015, 0.02, 0.03, 0.04, 0.05, 0.06,
                       0.08, 0.1, 0.15, 0.2, 0.3, 0.5)


def cache_one_precision_down() -> None:
    """Every model built from here on rounds what a sequence keeps between
    dispatches (K/V rows, tails) to float8 e4m3 before it is kept
    (models/zaya.py ``kept``).  As an operation of its own: a cast there and
    back is a pair of converts, which the TPU compiler may drop."""
    import jax

    from dynamo_tpu.models import zaya

    f8 = lambda x: jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    init = zaya.ZayaModel.__init__
    zaya.ZayaModel.__init__ = (
        lambda self, config, kept=f8: init(self, config, kept))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--check-seeds", default=None,
                   help="run only the benchmark's own check, once a seed "
                        "(comma separated), and print its margins")
    p.add_argument("--cache-one-precision-down", action="store_true",
                   help="with --check-seeds: the negative control")
    p.add_argument("--prompt", type=int, default=2048)
    p.add_argument("--answer", type=int, default=1024)
    p.add_argument("--seed", type=int, default=2**31 + 57)
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--tiny", action="store_true",
                   help="rehearse at a toy size on the CPU")
    a = p.parse_args(argv)
    control = "cache_one_precision_down" if a.cache_one_precision_down else None
    if a.check_seeds and "," in a.check_seeds:
        return recurrent.a_process_a_seed(
            __file__, a.check_seeds, control,
            (["--cache-one-precision-down"] if control else [])
            + (["--tiny"] if a.tiny else []))
    import jax

    from cellbench import spec

    if a.tiny:
        config = TINY
        a.prompt, a.answer = 200, 96
        a.num_blocks = config["serve"]["num_blocks"]
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(ROOT / "cellbench/configs" / f"{CONFIG}.json")
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    note(f"compile cache: {enable_persistent_cache()}")
    if a.check_seeds:
        import asyncio

        if control:
            cache_one_precision_down()
        rows = asyncio.run(recurrent.check_margins(
            config, [int(x) for x in a.check_seeds.split(",")]))
        print(json.dumps({"control": control, "checks": rows}), flush=True)
        return 0
    from cellbench import server
    from dynamo_tpu.engine import EngineCore

    model, core = serve(config, a.seed, a.num_blocks)
    ref = spec.load_module(ROOT, "reference", config["reference"])
    run = jax.jit(ref.make_forward(config))
    real = recurrent.long_answer(core, run, config, a, "served")
    # the control takes the engine's place: two pools do not fit the chip
    params, ecfg = core.params, core.config
    del core
    cache_one_precision_down()
    damaged = server.resolve(config["model_class"])(server.model_config(config))
    down = recurrent.long_answer(
        EngineCore(damaged, params, ecfg, eos_token_ids=[]), run, config, a,
        "cache_one_precision_down")
    # the rule's limits lie between the check's two readings at 17-700
    # tokens; over 2,048-3,072 both read lower (attention averages over more
    # rows), so the control is held to a multiple of the served median here
    ok = (all(part["ok"] for part in real["by_position"]) and real["all"]["ok"]
          and real["state_position_mismatches_total"] == 0
          and down["all"]["median"] > CONTROL_RATIO * real["all"]["median"])
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind,
                      "prompt": a.prompt, "answer": a.answer, "served": real,
                      "cache_one_precision_down": down}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
