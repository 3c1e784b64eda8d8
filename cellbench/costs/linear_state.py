"""Operations and bytes of the gated delta-rule state update, from shapes.

In every layer that does not attend (``num_hidden_layers`` less the
configuration's ``attention_layers``) a sequence keeps one float32 matrix
S [d, d] a head (``linear_attn_config``: H heads of width d).  The same work
whatever implements it, XLA fusions or a kernel:

  * a decode row reads and writes its state once, 2·H·d·d·4 bytes, beside
    its q, k, v, decay and output rows (5·H·d float32) and its step (H);
    the recurrence is ~7 operations a state element (decay 1, S'^T k 2, the
    rank-one update 2, S^T q 2);
  * a prefill chunk of ``take`` tokens reads and writes the state once a
    dispatch beside its rows, and computes the chunked (WY) form
    ``CHUNK`` tokens at a time: per chunk of C tokens and head 6·C·d·d for
    the three products with the state (K e^G S0, Q e^G S0, K^T U), 3·C²·d for
    the two causal score matrices under the per-channel decay (their lower
    triangles), 2·C²·d to apply them, C³/3 for the triangular system.

A call is ("d",) or ("p", take).  Decode rows and prefill chunks are rebuilt
from the client's records as costs/decode_attention.py and
costs/prefill_attention.py rebuild them.
"""

from pathlib import Path

from cellbench import spec

ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in
CHUNK = 64          # dynamo_tpu/ops/linear_state.py CHUNK
STATE_BYTES = 4     # float32


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    decode = spec.load_module(ROOT, "costs", "decode_attention")
    prefill = spec.load_module(ROOT, "costs", "prefill_attention")
    return ([("d",) for _ in decode.calls(records, interval, config)]
            + [("p", take) for take, _ in prefill.calls(records, interval, config)])


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    lin = config["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    layers = config["num_hidden_layers"] - config.get(
        "attention_layers", config["num_hidden_layers"])
    state = h * d * d
    row = 5 * h * d + h
    ops = nbytes = 0.0
    for call in calls_:
        if call[0] == "d":
            ops += 7.0 * state
            nbytes += 2.0 * state * STATE_BYTES + 4.0 * row
            continue
        take = call[1]
        whole, rest = divmod(take, CHUNK)
        for c in [CHUNK] * whole + ([rest] if rest else []):
            ops += h * (6.0 * c * d * d + 5.0 * c * c * d + c ** 3 / 3.0)
        nbytes += 2.0 * state * STATE_BYTES + 4.0 * row * take
    return layers * ops, layers * nbytes
