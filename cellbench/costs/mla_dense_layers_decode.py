"""Operations and bytes of dense latent decode attention in the layers that
attend alone: costs/mla_dense_decode.py, which counts every one of
``num_hidden_layers``, over the configuration's ``attention_layers`` — a
stack whose other layers keep a recurrent state.  What ``mla_dense_decode``'s
time is set against there."""

from pathlib import Path

from cellbench import spec

_every = spec.load_module(Path(__file__).resolve().parents[2], "costs",
                          "mla_dense_decode")
calls = _every.calls


def cost(config: dict, ctxs: list[int]) -> tuple[float, float]:
    return _every.cost(
        {**config, "num_hidden_layers": config["attention_layers"]}, ctxs)
