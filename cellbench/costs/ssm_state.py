"""Operations and bytes of the state-space (Mamba-2 SSD) state update, from
shapes.

In every layer that does not attend (``num_hidden_layers`` less the
configuration's ``attention_layers``) a sequence keeps one float32 matrix
S [P, N] a head (``mamba_n_heads`` H heads of width ``mamba_d_head`` P,
``mamba_d_state`` N, ``mamba_n_groups`` G groups of B and C).  The same work
whatever implements it, XLA fusions or a kernel:

  * a decode row reads and writes its state once, 2·H·P·N·4 bytes, beside
    its x and y rows (H·P each), B and C (G·N each) and its step (H), all
    float32; the recurrence is ~5 operations a state element (decay 1, the
    rank-one update 2, the read-out S C 2);
  * a prefill chunk of ``take`` tokens reads and writes the state once a
    dispatch beside its rows, and computes the chunked form
    ``mamba_chunk_size`` tokens at a time: per piece of Q tokens 2·Q²·N a
    group for C Bᵀ (once, not a head) and a head 2·Q²·P to apply the
    decayed scores and 4·Q·N·P for the two products with the state (the
    carried-in read-out C S_in and the update Σ (Δx) ⊗ B).

A call is ("d",) or ("p", take): the calls costs/linear_state.py rebuilds
from the client's records for the delta rule (decode rows and prefill chunks,
as costs/decode_attention.py and costs/prefill_attention.py find them).
"""

from pathlib import Path

from cellbench import spec

ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in
STATE_BYTES = 4     # float32


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    return spec.load_module(ROOT, "costs", "linear_state").calls(
        records, interval, config)


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    h, p, n, g = (config["mamba_n_heads"], config["mamba_d_head"],
                  config["mamba_d_state"], config["mamba_n_groups"])
    chunk = config["mamba_chunk_size"]
    layers = config["num_hidden_layers"] - config.get(
        "attention_layers", config["num_hidden_layers"])
    state = h * p * n
    row = 2 * h * p + 2 * g * n + h
    ops = nbytes = 0.0
    for call in calls_:
        if call[0] == "d":
            ops += 5.0 * state
            nbytes += 2.0 * state * STATE_BYTES + 4.0 * row
            continue
        take = call[1]
        whole, rest = divmod(take, chunk)
        for q in [chunk] * whole + ([rest] if rest else []):
            ops += g * 2.0 * q * q * n + h * (2.0 * q * q * p + 4.0 * q * n * p)
        nbytes += 2.0 * state * STATE_BYTES + 4.0 * row * take
    return layers * ops, layers * nbytes
