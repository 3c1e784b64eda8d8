"""Operations and bytes of the selective state-space (Mamba-1) decode step,
from shapes.

In every layer that does not attend (``num_hidden_layers`` less the
configuration's ``attention_layers``) a slot keeps a float32 state h [N, I]
(``mamba_d_state`` N numbers for each of the I = ``mamba_expand`` x
``hidden_size`` channels) and the convolution's tail, the last
``mamba_d_conv`` - 1 inputs of I channels in the served dtype.  The same work
whatever implements it, XLA fusions or a kernel:

  * a decode row reads and writes both once — ``slot_bytes``: 2·N·I·4 +
    2·(K-1)·I·2 = 716,800 B at 16 x 5,120 and K 4 — beside its x̂, Δ and y
    rows (I float32 each) and its B and C (N each);
  * the recurrence is ~6 operations and one exponential a state element
    (Δ·A, the decay, the update's two, the read-out's two): far under the
    bytes at any peak the benchmark has.

A call is one decode row, as costs/decode_attention.py finds them.  The
prefill chunks' scan is another program class with a cost module and a metric
of its own (costs/selective_scan.py).
"""

from pathlib import Path

from cellbench import spec

ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in
STATE_BYTES = 4     # float32
BYTES = {"bfloat16": 2, "float32": 4}


def geometry(config: dict) -> tuple[int, int, int, int]:
    """(recurrent layers, N, I, K)."""
    layers = config["num_hidden_layers"] - config.get(
        "attention_layers", config["num_hidden_layers"])
    return (layers, config["mamba_d_state"],
            config["mamba_expand"] * config["hidden_size"],
            config["mamba_d_conv"])


def slot_bytes(config: dict) -> int:
    """What one recurrent layer moves of one slot's own in a dispatch: the
    state and the convolution's tail, each read once and written once."""
    _, n, inner, kk = geometry(config)
    tail = (kk - 1) * inner * BYTES[config.get("dtype", "bfloat16")]
    return 2 * n * inner * STATE_BYTES + 2 * tail


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    decode = spec.load_module(ROOT, "costs", "decode_attention")
    return [("d",) for _ in decode.calls(records, interval, config)]


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    layers, n, inner, _ = geometry(config)
    rows = float(len(calls_))
    ops = rows * 6.0 * n * inner
    nbytes = rows * (slot_bytes(config) + 4.0 * (3 * inner + 2 * n))
    return layers * ops, layers * nbytes
