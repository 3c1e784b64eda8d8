"""Operations and bytes of the sparse-attention indexer, from shapes.

In every layer whose ``indexer_types`` entry is ``full`` a query token scores
each of the ``ctx`` positions it can see: ``index_n_heads`` dot products of
``index_head_dim`` elements with the position's one indexer key,
2·Hi·Di·ctx operations, and the keys, ctx·Di elements, must move.  Choosing
the top-k from the scores is not counted as work.  A decode row is one query
at its context; the ``take`` queries of a prefill chunk after ``prefix``
cached tokens share one reading of the prefix + take keys.  The computed part
of a prompt is taken as costs/mla_sparse_prefill.py takes it.

A call is ("d", ctx) or ("p", take, prefix).
"""

from pathlib import Path

from cellbench import spec

BYTES = {"bfloat16": 2, "float32": 4}
ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    decode = spec.load_module(ROOT, "costs", "mla_sparse_decode")
    prefill = spec.load_module(ROOT, "costs", "mla_sparse_prefill")
    return ([("d", c) for c in decode.calls(records, interval, config)]
            + [("p", *c) for c in prefill.calls(records, interval, config)])


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    full = sum(t == "full" for t in config["indexer_types"])
    hi, di = config["index_n_heads"], config["index_head_dim"]
    el = BYTES[config.get("dtype", "bfloat16")]
    pairs = keys = 0
    for call in calls_:
        if call[0] == "d":
            pairs += call[1]
            keys += call[1]
        else:
            _, take, prefix = call
            pairs += take * prefix + take * (take + 1) // 2
            keys += prefix + take
    return full * 2.0 * hi * di * pairs, full * float(el) * di * keys
