"""Operations and bytes of dense latent prefill attention in the layers that
attend alone: costs/mla_dense_prefill.py over the configuration's
``attention_layers`` (costs/mla_dense_layers_decode.py says why)."""

from pathlib import Path

from cellbench import spec

_every = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                          "mla_dense_prefill")
calls = _every.calls


def cost(config: dict, calls_: list[tuple[int, int]]) -> tuple[float, float]:
    return _every.cost(
        {**config, "num_hidden_layers": config["attention_layers"]}, calls_)
