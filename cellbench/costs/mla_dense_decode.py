"""Operations and bytes of dense latent decode attention, from shapes.

One call = one row of one decode step: a single query token at context
``ctx`` attends, in every layer, to all ``ctx`` cached rows.  Absorbed form,
per layer and row: each of the Hq heads scores the row over kv_lora_rank +
qk_rope_head_dim elements and adds kv_lora_rank elements of it to its sum:
2·Hq·(r + d_rope) + 2·Hq·r operations.  Bytes that must move: the context's
rows, (r + d_rope) elements each, held once — what the algorithm needs; the
padding of the program's cache row is the program's cost — plus the query and
the output of every head.  With 32 heads sharing each row the kernel sits at
~58 operations a byte, near both of a v5e's limits (240 a byte at the ridge).
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
    layers, hq = config["num_hidden_layers"], config["num_attention_heads"]
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    el = BYTES[config.get("dtype", "bfloat16")]
    rows = sum(ctxs)
    ops = layers * 2.0 * hq * ((r + rope) + r) * rows
    nbytes = layers * el * ((r + rope) * rows
                            + hq * ((r + rope) + r) * len(ctxs))
    return ops, nbytes
