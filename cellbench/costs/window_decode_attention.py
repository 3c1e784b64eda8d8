"""Operations and bytes of paged decode attention in a stack whose layers are
of two kinds (``layer_types``), from shapes.

One call = one row of one decode step: a single query token at context
``ctx``.  A ``full_attention`` layer attends to all ``ctx`` cached rows, a
``sliding_attention`` layer to the last min(ctx, ``sliding_window``) of them
(query p sees key j iff 0 <= p - j < window).  Per layer and attended row:
QK^T and PV are 2·Hq·D operations each; the bytes that must move are the
row's key and value (2·Hk·D elements), plus the query and the output
(2·Hq·D) once a call and layer.  Rows are counted, never blocks: the partial
block at the band's older edge, which the kernel fetches whole, is the
program's cost, so a share reads low, never over 100.

``cost(config, ctxs, kinds=...)`` sums the layers of the kinds named (both by
default); costs/window_layers_decode_attention.py is the window layers alone.
"""

from pathlib import Path

from cellbench import spec

# one call = one decode token's context, as every decode cost module counts
calls = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                         "decode_attention").calls

BYTES = {"bfloat16": 2, "float32": 4}
KINDS = ("sliding_attention", "full_attention")


def attended(config: dict, kind: str, ctx: int) -> int:
    """Cached rows one query at context ``ctx`` reads in a layer of ``kind``."""
    window = config.get("sliding_window")
    if kind == "sliding_attention" and window:
        return min(ctx, window)
    return ctx


def cost(config: dict, ctxs: list[int], kinds=KINDS) -> tuple[float, float]:
    hq, hk, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    el = BYTES[config.get("dtype", "bfloat16")]
    ops = nbytes = 0.0
    for kind in config["layer_types"]:
        if kind not in kinds:
            continue
        rows = sum(attended(config, kind, c) for c in ctxs)
        ops += 4.0 * hq * d * rows
        nbytes += el * (2.0 * hk * d * rows + 2.0 * hq * d * len(ctxs))
    return ops, nbytes
