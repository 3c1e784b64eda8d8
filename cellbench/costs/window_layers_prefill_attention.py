"""Operations and bytes of prefill attention in the ``sliding_attention``
layers of ``layer_types`` alone: costs/window_prefill_attention.py with the
full layers left out.  What the windowed prefill kernel's time
(``paged_prefill_attention_window*``) is set against."""

from pathlib import Path

from cellbench import spec

_both = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                         "window_prefill_attention")
calls = _both.calls


def cost(config: dict, calls_: list[tuple[int, int]]) -> tuple[float, float]:
    return _both.cost(config, calls_, kinds=("sliding_attention",))
