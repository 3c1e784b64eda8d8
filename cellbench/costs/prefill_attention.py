"""Operations and bytes of (chunked) causal prefill attention, from shapes.

One call = one chunk of ``take`` new tokens after ``prefix`` cached ones.
Layers are those that attend: ``attention_layers`` of the configuration, all
``num_hidden_layers`` where it has no such key (costs/decode_attention.py).
Per layer the causal scores the algorithm needs are take·prefix +
take·(take+1)/2 query-key pairs, each 2·D operations for QK^T and 2·D for
PV, in each of Hq heads.  Bytes: queries and outputs of the chunk
(2·Hq·D·take), keys and values of prefix and chunk (2·Hk·D·(prefix+take)).
"""

BYTES = {"bfloat16": 2, "float32": 4}


def chunks(prompt_len: int, chunk: int) -> list[tuple[int, int]]:
    out, done = [], 0
    while done < prompt_len:
        take = min(prompt_len - done, chunk or prompt_len)
        out.append((take, done))
        done += take
    return out


def calls(records: list, interval: tuple, config: dict) -> list[tuple[int, int]]:
    """The chunks of every prompt whose first token arrived in the interval."""
    t0, t1 = interval
    chunk = int(config["serve"].get("prefill_chunk_tokens", 0))
    return [c for r in records
            if r["first"] is not None and t0 <= r["first"] < t1
            for c in chunks(r["prompt_len"], chunk)]


def cost(config: dict, calls_: list[tuple[int, int]]) -> tuple[float, float]:
    layers = config.get("attention_layers", config["num_hidden_layers"])
    hq, hk, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    el = BYTES[config.get("dtype", "bfloat16")]
    pairs = sum(take * prefix + take * (take + 1) / 2 for take, prefix in calls_)
    ops = layers * 4.0 * hq * d * pairs
    nbytes = layers * el * sum(
        2.0 * hq * d * take + 2.0 * hk * d * (prefix + take)
        for take, prefix in calls_)
    return ops, nbytes
