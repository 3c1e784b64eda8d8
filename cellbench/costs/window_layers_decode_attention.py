"""Operations and bytes of decode attention in the ``sliding_attention``
layers of ``layer_types`` alone: costs/window_decode_attention.py with the
full layers left out.  What the windowed decode kernel's time
(``paged_decode_attention_window*``) is set against."""

from pathlib import Path

from cellbench import spec

_both = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                         "window_decode_attention")
calls = _both.calls


def cost(config: dict, ctxs: list[int]) -> tuple[float, float]:
    return _both.cost(config, ctxs, kinds=("sliding_attention",))
