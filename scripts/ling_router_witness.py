"""Where ling-3.0-flash-ep4's distance from its reference comes from: the
router's picks, counted a position, and the reference scored again with the
program's picks forced on it (PERF.md section 6, PR 66).

    chiprun --timeout 1800 -- python3 scripts/ling_router_witness.py \
        [--seeds a,b] [--control] [--published-layers 0,6,7,11]
        [--dtype float32] [--tiny]

One process, on the chip (``--tiny``: the toy of
scripts/hybrid_linear_longctx_check.py in bf16 on the CPU, to rehearse).  It
builds cellbench/configs/ling-3.0-flash-ep4.json at its published widths
with seeded weights and runs the benchmark check's own four prompts (17-700
tokens, 8 greedy tokens behind each) through ``HybridLinearModel.forward``
by direct calls, as the engine lays them out: prefill in chunks of
``prefill_chunk_tokens`` into one slot, then a decode step a token over the
slot array.  ``moe_route`` is wrapped so that every expert layer hands its
picks to the host (a ``jax.debug.callback``: the arithmetic is untouched).
The reference (cellbench/reference/ling_hybrid_mla.py, float32) runs over
the same tokens twice: with its own picks, which it reports, and with the
program's picks in place of them (the weights still from its own scores).

For every position of every sequence, |program - reference| of the
log-probability of the program's 20 likeliest tokens, as the check compares
them, and whether the two sides picked the same experts there:

  * ``layer_positions_differ``: the share of (expert layer, position) whose
    picks *among the experts held here* differ between program and reference;
  * ``clean`` / ``after_a_difference``: the distances at positions up to and
    including which no layer differed, and at the others;
  * ``forced``: the distances when the reference is given the program's
    picks: what is left is rounding that moved no pick.

``--control`` runs the program once more with everything a sequence keeps
one precision down (the long-context script's ``cache_one_precision_down``)
and scores it both ways: under forced picks the cache is what is left.
``--dtype float32`` is the second witness: the program's own code in float32
(at the highest matmul precision, ``DYNAMO_DISABLE_PALLAS=1`` in the
environment) on a cut of the stack whose float32 weights fit the chip
(``--published-layers 0,6,7,11``: K + dense, K, K, M; 11.1 GB) — if the
program's routed part were at fault and not its rounding, float32 would keep
the distance.  The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from scripts.glm_longctx_check import note  # noqa: E402
from scripts.hybrid_linear_longctx_check import (  # noqa: E402
    TINY_LING, cache_one_precision_down)

SLOTS = 4           # the slot array of the decode steps; the sequence in slot 1
SLOT = 1
TOP = 20            # candidates a position, as the check asks for

PICKS: list = []    # what the wrapped ``moe_route`` handed over, call by call


def hand_over_picks() -> None:
    """Wrap the router the model calls: its picks [rows, k] go to ``PICKS``
    in the order the layers run."""
    import jax
    import numpy as np

    from dynamo_tpu.models import hybrid_linear

    route = hybrid_linear.moe_route

    def moe_route(cfg, router, x, bias=None):
        weights, topi = route(cfg, router, x, bias)
        jax.debug.callback(lambda t: PICKS.append(np.asarray(t)), topi,
                           ordered=True)
        return weights, topi

    hybrid_linear.moe_route = moe_route


def program(model):
    """``model.forward`` with the log-softmax of its logits behind it, jitted:
    a prefill chunk is one row that names its slot, a decode step is over the
    slot array (``seq_slots`` None)."""
    import jax

    def run(params, tokens, positions, cache, tables, lens, slots,
            seq_slots=None):
        hidden, cache = model.forward(params, tokens, positions, cache, tables,
                                      lens, slots, seq_slots=seq_slots)
        return jax.nn.log_softmax(
            model.compute_logits(params, hidden), axis=-1), cache

    return jax.jit(run)


def greedy(model, run, params, prompt: list[int], n: int, chunk: int, bs: int):
    """The prompt as prefill chunks of ``chunk`` tokens into slot ``SLOT``,
    then n - 1 decode steps over the slot array, each fed the likeliest
    token of the step before.  Returns (the tokens fed — the prompt and all
    but the last generated token —, log-probabilities [T, V], picks
    [expert layers, T, k])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    total = len(prompt) + n - 1
    width = -(-(total + 1) // bs)
    cache = model.init_kv_cache(width + 2, bs, slots=SLOTS)
    table = 1 + np.arange(width, dtype=np.int32)

    def slot_of(p):
        return table[p // bs] * bs + p % bs

    def picks_of(rows):
        jax.effects_barrier()
        out = np.stack([p[rows] for p in PICKS])
        del PICKS[:]
        return out

    fed, logp, picks = list(prompt), [], []
    for a in range(0, len(prompt), chunk):
        b = min(len(prompt), a + chunk)
        tok = np.zeros((1, chunk), np.int32)
        pos = np.zeros((1, chunk), np.int32)
        slots = np.full((1, chunk), -1, np.int32)
        tok[0, :b - a], pos[0, :b - a] = fed[a:b], np.arange(a, b)
        slots[0, :b - a] = slot_of(np.arange(a, b))
        lp, cache = run(
            params, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(table[None]), jnp.asarray([b], jnp.int32),
            jnp.asarray(slots), jnp.asarray([SLOT], jnp.int32))
        logp.append(np.asarray(lp[0, :b - a], np.float32))
        picks.append(picks_of(slice(0, b - a)))
    tables = np.zeros((SLOTS, width), np.int32)
    tables[SLOT] = table
    for p in range(len(prompt), total):
        fed.append(int(np.argmax(logp[-1][-1])))
        tok = np.zeros((SLOTS, 1), np.int32)
        pos = np.zeros((SLOTS, 1), np.int32)
        slots = np.full((SLOTS, 1), -1, np.int32)
        lens = np.zeros(SLOTS, np.int32)
        tok[SLOT, 0], pos[SLOT, 0], lens[SLOT] = fed[p], p, p + 1
        slots[SLOT, 0] = slot_of(p)
        lp, cache = run(params, jnp.asarray(tok), jnp.asarray(pos), cache,
                        jnp.asarray(tables), jnp.asarray(lens),
                        jnp.asarray(slots))
        logp.append(np.asarray(lp[SLOT], np.float32))
        picks.append(picks_of(slice(SLOT, SLOT + 1)))
    return fed, np.concatenate(logp), np.concatenate(picks, axis=1)


def make_reference(config: dict):
    """``f(params, tokens [T], forced [L, T, E] or None) -> (log-probabilities
    [T, V], picks [L, T, E] bool)``: the benchmark's reference, its router
    reporting what it picked or taking the picks it is given."""
    import jax
    import jax.numpy as jnp

    from cellbench import spec

    ref = spec.load_module(ROOT, "reference", config["reference"])
    own = ref.gates

    def run(params, tokens, forced):
        seen = []

        def gates(x, lp, cfg):
            g = own(x, lp, cfg)
            if forced is not None:
                s = jax.nn.sigmoid(x @ ref.f32(lp["router"]))
                g = forced[len(seen)] * s
                g = (g / (g.sum(-1, keepdims=True) + 1e-20)
                     * cfg.get("routed_scaling_factor", 1.0))
            seen.append(g > 0)
            return g

        ref.gates = gates
        try:
            logp = ref.forward(params, tokens, jnp.arange(tokens.shape[0]),
                               config)
        finally:
            ref.gates = own
        return logp, jnp.stack(seen)

    return jax.jit(run)


def margins(deltas) -> dict:
    import numpy as np

    d = np.asarray(deltas, np.float64).ravel()
    if not d.size:
        return {"pairs": 0}
    return {"pairs": int(d.size), "median": float(np.median(d)),
            "within_0.05": float((d <= 0.05).mean()),
            "within_0.5": float((d <= 0.5).mean()), "max": float(d.max())}


def witness(model, params, reference, config: dict, prompts, n: int) -> dict:
    """The four prompts through the program and both readings of the
    reference; the margins over all positions and over the generated ones
    (what the check sees)."""
    import jax.numpy as jnp
    import numpy as np

    serve = config["serve"]
    first = int(config["expert_parallel"]["first_expert"])
    held = slice(first, first + int(config["num_experts"]))
    experts = int(config["expert_parallel"]["router_experts"])
    every = {k: [] for k in ("free", "forced", "clean", "after_a_difference",
                             "free_generated", "forced_generated")}
    differ = total = differ_any = positions = dirty_positions = 0
    run = program(model)
    for prompt in prompts:
        fed, logp, picks = greedy(model, run, params, prompt, n,
                                  serve["prefill_chunk_tokens"],
                                  serve["block_size"])
        t = len(fed)
        padded = np.zeros(-(-t // 128) * 128, np.int32)
        padded[:t] = fed
        chosen = np.zeros((picks.shape[0], len(padded), experts), np.float32)
        np.put_along_axis(chosen[:, :t], picks, 1.0, axis=-1)
        free, ref_picks = reference(params, jnp.asarray(padded), None)
        forced, _ = reference(params, jnp.asarray(padded), jnp.asarray(chosen))
        top = np.argsort(-logp, axis=-1)[:, :TOP]
        ours = np.take_along_axis(logp, top, axis=-1)
        d_free = np.abs(ours - np.take_along_axis(
            np.asarray(free)[:t], top, axis=-1))
        d_forced = np.abs(ours - np.take_along_axis(
            np.asarray(forced)[:t], top, axis=-1))
        here = (np.asarray(ref_picks)[:, :t, held]
                != (chosen[:, :t, held] > 0)).any(-1)          # [L, T]
        anywhere = (np.asarray(ref_picks)[:, :t]
                    != (chosen[:, :t] > 0)).any(-1)
        dirty = np.maximum.accumulate(here.any(0))             # [T]
        differ += int(here.sum())
        differ_any += int(anywhere.sum())
        total += here.size
        positions += t
        dirty_positions += int(here.any(0).sum())
        gen = slice(len(prompt) - 1, t)
        every["free"].append(d_free.ravel())
        every["forced"].append(d_forced.ravel())
        every["clean"].append(d_free[~dirty].ravel())
        every["after_a_difference"].append(d_free[dirty].ravel())
        every["free_generated"].append(d_free[gen].ravel())
        every["forced_generated"].append(d_forced[gen].ravel())
        note(f"prompt {len(prompt)}: free {np.median(d_free):.4f}"
             f" forced {np.median(d_forced):.4f}"
             f" positions with a held pick differing {int(here.any(0).sum())}"
             f"/{t}, first at {int(np.argmax(dirty)) if dirty.any() else None}")
    out = {k: margins(np.concatenate(v)) for k, v in every.items()}
    out["layer_positions_differ"] = differ / total
    out["layer_positions_differ_any_expert"] = differ_any / total
    out["positions_differ"] = dirty_positions / positions
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", default=str(2**31 + 66))
    p.add_argument("--control", action="store_true",
                   help="also the program with its cache one precision down")
    p.add_argument("--published-layers", default=None,
                   help="a shorter cut of the stack, e.g. 0,6,7,11")
    p.add_argument("--dtype", default=None,
                   help="float32: the program's own float32 copy as witness "
                        "(with --published-layers 0,6,7,11 its weights fit)")
    p.add_argument("--tiny", action="store_true",
                   help="rehearse at a toy size on the CPU")
    a = p.parse_args(argv)
    import jax

    from cellbench import check, server, spec

    if a.tiny:
        config = dict(TINY_LING, dtype="bfloat16")
    else:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("no TPU: the published widths are compared on "
                             "the chip (--tiny rehearses on the CPU)")
        config = spec.read_json(
            ROOT / "cellbench/configs/ling-3.0-flash-ep4.json")
    if a.published_layers:
        layers = [int(x) for x in a.published_layers.split(",")]
        config = dict(config, published_layers=layers,
                      num_hidden_layers=len(layers))
    if a.dtype:
        config = dict(config, dtype=a.dtype)
    if config["dtype"] == "float32":
        jax.config.update("jax_default_matmul_precision", "highest")
    settings = spec.load_settings(ROOT)
    gen = spec.load_module(ROOT, "generators", "mix_fixed_order")
    n = int(settings["check"]["max_tokens"])
    hand_over_picks()
    reference = make_reference(config)
    build = lambda: server.resolve(config["model_class"])(
        server.model_config(config))
    rows = [{"seed": int(s)} for s in a.seeds.split(",")]
    for name in ["program"] + ["cache_one_precision_down"] * a.control:
        if name != "program":
            cache_one_precision_down()
        for row in rows:
            model = build()
            # one set of weights on the chip at a time
            params = None
            params = server.make_params(model, row["seed"], None)
            prompts = check.check_prompts(settings, row["seed"],
                                          config["vocab_size"], gen)
            row[name] = witness(model, params, reference, config, prompts, n)
            note(f"seed {row['seed']} {name}: {json.dumps(row[name])}")
    print(json.dumps({"device": jax.devices()[0].device_kind, "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
