"""Operations and bytes of the delta rule's decode step alone, from shapes.

One program class: the decode step's state update over the slot array
(``linear_state_update``).  In every layer that does not attend
(``num_hidden_layers`` less ``attention_layers``) a decode row reads and
writes its float32 state S [d, d] a head once — 2·H·d·d·4 bytes, H =
``num_attention_heads`` heads of ``head_dim`` (as many key/value heads as
query heads) — beside its q, k, v, decay and output rows (5·H·d float32) and
its step (H); the recurrence is ~7 operations a state element (the decay 1,
S'^T k 2, the rank-one update 2, S^T q 2): far under the bytes at any peak
the benchmark has.  costs/linear_state.py counts the same row and, with it,
the prefill chunks' scan — another program class, whose XLA fusions the
kernel's name does not match; this module leaves them out.

A call is one decode row, as costs/decode_attention.py finds them.
"""

from pathlib import Path

from cellbench import spec

ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in
STATE_BYTES = 4     # float32


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    decode = spec.load_module(ROOT, "costs", "decode_attention")
    return [("d",) for _ in decode.calls(records, interval, config)]


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    h, d = config["num_attention_heads"], config["head_dim"]
    layers = config["num_hidden_layers"] - config["attention_layers"]
    state = h * d * d
    rows = float(len(calls_))
    ops = rows * 7.0 * state
    nbytes = rows * (2.0 * state * STATE_BYTES + 4.0 * (5 * h * d + h))
    return layers * ops, layers * nbytes
