"""Operations and bytes of paged decode attention, from shapes.

One call = one row of one decode step: a single query token attends to a
context of ``ctx`` tokens in every layer that attends.  That is
``attention_layers`` of the configuration, or all ``num_hidden_layers`` where
it has no such key: a stack in which some layers are of another kind states
how many attend.  Per attending layer: QK^T and PV are 2·Hq·D·ctx operations
each; the bytes that must move are the context's keys and values (2·Hk·D·ctx
elements) plus the query and the output (2·Hq·D).
Decode attention is bound by those bytes on every current chip.
"""

BYTES = {"bfloat16": 2, "float32": 4}


def calls(records: list, interval: tuple, config: dict) -> list[int]:
    """Context length of every decode token that arrived in the interval:
    token j >= 1 of a request came from a decode step over prompt + j."""
    t0, t1 = interval
    return [r["prompt_len"] + j
            for r in records for j, t in enumerate(r["token_times"])
            if j >= 1 and t0 <= t < t1]


def cost(config: dict, ctxs: list[int]) -> tuple[float, float]:
    layers = config.get("attention_layers", config["num_hidden_layers"])
    hq, hk, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    el = BYTES[config.get("dtype", "bfloat16")]
    total = sum(ctxs)
    ops = layers * 4.0 * hq * d * total
    nbytes = layers * el * (2.0 * hk * d * total + 2.0 * hq * d * len(ctxs))
    return ops, nbytes
