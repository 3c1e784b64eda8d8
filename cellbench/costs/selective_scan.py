"""Operations and bytes of the selective state-space (Mamba-1) scan of a
prefill chunk, from shapes.

The decay differs by channel and state index, so a chunk has no
matrix-product form: the work is the decode step's (costs/selective_step.py),
a token after a token, ~6 operations and one exponential a state element —
6·N·I a token and recurrent layer, all of it on the vector unit.  The bytes
the algorithm needs are the slot's own (``selective_step.slot_bytes``: the
state and the convolution's tail once in and once out a chunk) beside, a
token, x̂ and Δ read and y written (I float32 each) and B and C (N each): the
decay exp(Δ·A), the update and the state after each token never reach memory.

``peaks.json`` has a peak for the matrix unit and for the memory, none for
the vector unit: the least time taken from these counts is the bytes' (a 512
token chunk: ~32 MB a layer, ~40 us), several times under what the vector
unit needs for 512 x 81,920 exponentials, so a share computed from it reads
low by construction (PERF.md section 7).

A call is ("p", take): one prefill chunk of ``take`` tokens, as
costs/prefill_attention.py finds them.
"""

from pathlib import Path

from cellbench import spec

ROOT = Path(__file__).resolve().parents[2]      # the data root this file is in


def calls(records: list, interval: tuple, config: dict) -> list[tuple]:
    prefill = spec.load_module(ROOT, "costs", "prefill_attention")
    return [("p", take) for take, _ in prefill.calls(records, interval, config)]


def cost(config: dict, calls_: list[tuple]) -> tuple[float, float]:
    step = spec.load_module(ROOT, "costs", "selective_step")
    layers, n, inner, _ = step.geometry(config)
    tokens = float(sum(take for _, take in calls_))
    ops = tokens * 6.0 * n * inner
    nbytes = (len(calls_) * float(step.slot_bytes(config))
              + tokens * 4.0 * (3 * inner + 2 * n))
    return layers * ops, layers * nbytes
