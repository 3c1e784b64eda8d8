"""Operations and bytes of dense latent prefill attention, from shapes.

One call = one chunk of ``take`` query tokens after ``prefix`` cached ones;
the query at position p attends to all p + 1 rows up to its own, at the
per-row operations of costs/mla_dense_decode.py.  Bytes that must move: the
chunk's context (prefix + take rows of r + d_rope elements), read once a
chunk and layer, plus the queries and outputs of every head.

It counts only the tokens the engine computes.  The records carry
``prompt_len`` alone, so the part not served from the prefix cache is taken
from the traffic's shape: generators/shared_docs.py makes a prompt of a
document whose length is a multiple of 8,192 tokens and a question shorter
than that, and in the window every document is resident, so the computed
part is ``prompt_len mod 8192`` (the question) after a prefix of the rest: a
question's prefill is counted over document + question.  A prompt shorter
than 8,192 is computed whole.  A missed prefix hit — a document prefilled
inside the traced slice — then adds time this module counts no work for: the
share reads low, never over 100%.
"""

DOC_UNIT = 8192
BYTES = {"bfloat16": 2, "float32": 4}


def computed(prompt_len: int) -> tuple[int, int]:
    """(tokens the engine computes, cached tokens before them)."""
    take = prompt_len % DOC_UNIT if prompt_len >= DOC_UNIT else prompt_len
    return take, prompt_len - take


def chunks(prompt_len: int, chunk: int) -> list[tuple[int, int]]:
    todo, done = computed(prompt_len)
    out = []
    while todo:
        take = min(todo, chunk or todo)
        out.append((take, done))
        done, todo = done + take, todo - take
    return out


def calls(records: list, interval: tuple, config: dict) -> list[tuple[int, int]]:
    """The computed chunks of every prompt whose first token arrived in the
    interval."""
    t0, t1 = interval
    chunk = int(config["serve"].get("prefill_chunk_tokens", 0))
    return [c for r in records
            if r["first"] is not None and t0 <= r["first"] < t1
            for c in chunks(r["prompt_len"], chunk)]


def rows(take: int, prefix: int) -> int:
    """Cache rows the chunk's queries attend to, summed: the query at
    prefix + t sees prefix + t + 1 of them."""
    return take * prefix + take * (take + 1) // 2


def cost(config: dict, calls_: list[tuple[int, int]]) -> tuple[float, float]:
    layers, hq = config["num_hidden_layers"], config["num_attention_heads"]
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    el = BYTES[config.get("dtype", "bfloat16")]
    n_rows = sum(rows(take, prefix) for take, prefix in calls_)
    n_context = sum(prefix + take for take, prefix in calls_)
    n_queries = sum(take for take, _ in calls_)
    ops = layers * 2.0 * hq * ((r + rope) + r) * n_rows
    nbytes = layers * el * ((r + rope) * n_context
                            + hq * ((r + rope) + r) * n_queries)
    return ops, nbytes
