"""Operations and bytes of (chunked) causal prefill attention in a stack
whose layers are of two kinds (``layer_types``), from shapes.

One call = one chunk of ``take`` query tokens after ``prefix`` cached ones.
The query at position p attends to p + 1 rows in a ``full_attention`` layer
and to min(p + 1, ``sliding_window``) in a ``sliding_attention`` layer; each
attended row is 2·D operations for QK^T and 2·D for PV in each of Hq heads.
Bytes that must move, a chunk and layer: keys and values of the rows any of
the chunk's queries attends to (prefix + take in a full layer; in a window
layer the chunk and the window - 1 rows before it, where there are as many),
2·Hk·D elements each, plus the queries and outputs (2·Hq·D·take).  Rows, never
blocks or tiles, so a share reads low, never over 100.

It counts only the tokens the engine computes, by
costs/mla_dense_prefill.py's rule for this traffic (its ``calls``: a prompt
of a resident document and a question computes ``prompt_len mod 8192`` tokens
after a cached prefix of the rest).

``cost(config, calls, kinds=...)`` sums the layers of the kinds named (both
by default); costs/window_layers_prefill_attention.py is the window layers
alone.
"""

from pathlib import Path

from cellbench import spec

_prefix_rule = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                                "mla_dense_prefill")
calls = _prefix_rule.calls

BYTES = {"bfloat16": 2, "float32": 4}
KINDS = ("sliding_attention", "full_attention")


def attended(window, take: int, prefix: int) -> tuple[int, int]:
    """(rows the chunk's queries attend to, summed; rows of context any of
    them reads) under a window of ``window`` rows (None: none)."""
    if not window:
        return take * prefix + take * (take + 1) // 2, prefix + take
    # queries at prefix .. prefix + take - 1; the first ``ramp`` of them
    # still see fewer than ``window`` rows
    ramp = min(max(window - prefix - 1, 0), take)
    rows = ramp * prefix + ramp * (ramp + 1) // 2 + (take - ramp) * window
    return rows, min(prefix, window - 1) + take


def cost(config: dict, calls_: list[tuple[int, int]],
         kinds=KINDS) -> tuple[float, float]:
    hq, hk, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    el = BYTES[config.get("dtype", "bfloat16")]
    ops = nbytes = 0.0
    for kind in config["layer_types"]:
        if kind not in kinds:
            continue
        window = (config.get("sliding_window")
                  if kind == "sliding_attention" else None)
        for take, prefix in calls_:
            rows, context = attended(window, take, prefix)
            ops += 4.0 * hq * d * rows
            nbytes += el * (2.0 * hk * d * context + 2.0 * hq * d * take)
    return ops, nbytes
