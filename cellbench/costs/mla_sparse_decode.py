"""Operations and bytes of sparse latent decode attention, from shapes.

One call = one row of one decode step: a single query token at context
``ctx`` attends, in every layer, to the min(ctx, index_topk) cache rows its
indexer selected.  Absorbed form, per layer and selected row: each of the Hq
heads scores the row over kv_lora_rank + qk_rope_head_dim elements and adds
kv_lora_rank elements of it to its sum: 2·Hq·(r + d_rope) + 2·Hq·r
operations.  Bytes that must move: the selected rows, (r + d_rope) elements
each, held once; plus the query and the output of every head.  Bound by the
bytes on every current chip.  The indexer that chose the rows is another
operation (costs/dsa_indexer.py).
"""

BYTES = {"bfloat16": 2, "float32": 4}


def calls(records: list, interval: tuple, config: dict) -> list[int]:
    """Context length of every decode token that arrived in the interval:
    token j >= 1 of a request came from a decode step over prompt + j."""
    t0, t1 = interval
    return [r["prompt_len"] + j
            for r in records for j, t in enumerate(r["token_times"])
            if j >= 1 and t0 <= t < t1]


def shape(config: dict) -> tuple[int, int, int, int, int]:
    return (config["num_hidden_layers"], config["num_attention_heads"],
            config["kv_lora_rank"], config["qk_rope_head_dim"],
            config["index_topk"])


def cost(config: dict, ctxs: list[int]) -> tuple[float, float]:
    layers, hq, r, rope, topk = shape(config)
    el = BYTES[config.get("dtype", "bfloat16")]
    rows = sum(min(c, topk) for c in ctxs)
    ops = layers * 2.0 * hq * ((r + rope) + r) * rows
    nbytes = layers * el * ((r + rope) * rows
                            + hq * ((r + rope) + r) * len(ctxs))
    return ops, nbytes
