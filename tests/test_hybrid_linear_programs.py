"""The engine's part of a recurrent state (``seq_slots`` through
``unified_step``, the slot-indexed leaves of the cache) adds no operation to
the programs of a model without one, and the programs of the model with one
have the shape the engine counts on."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.core import multi_decode_step, unified_step
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

# sha256 of the lowered text of the two serving programs (``unified_step``:
# a 16-token chunk behind one cached block; ``multi_decode_step``: 4 rows) on
# the parent commit 0b029b6, for the Mistral toy (ModelConfig.tiny(), GQA
# 4/2) and the latent-attention toy with an indexer (tests/test_glm_dsa.py's
# TINY).  After a change meant to alter every model's program, print the new
# ones with ``PYTHONPATH=. python tests/test_hybrid_linear_programs.py``.
# (PR 64 was one: the four "decode" digests in this file are of the decode
# step without the scan of one step round it; the prefill ones are older.)
PARENT_HLO = {
    ("llama", "prefill"):
        "3048b264db3fb244f18787ac7e9735d15deebf89b10cedd2e923f1ce085fb3e6",
    ("llama", "decode"):
        "1c06068a871cd521756e60edfbcbdc40977683e5124c4b41766fc33f96fb90f4",
    ("glm", "prefill"):
        "bcbb250b0ded0d4365c61abc7186c417890c71baab55c0dfa040bbde756ca133",
    ("glm", "decode"):
        "e0a8b97a5dbcc9f806413a877bf2ba471ea09aa012e41ba26d4533a44364fe7f",
}
BS, M, B = 16, 4, 4


def _toy(family: str):
    if family == "llama":
        return LlamaModel(ModelConfig.tiny(num_kv_heads=2))
    from test_glm_dsa import TINY
    from dynamo_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
    return GlmDsaModel(GlmDsaConfig.from_hf_config(TINY, dtype="float32"))


def _lowered(model, program: str, **extra) -> str:
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    slots = {"slots": B} if getattr(model, "recurrent_state", False) else {}
    cache = jax.eval_shape(lambda: model.init_kv_cache(8, BS, **slots))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if program == "prefill":
        fn = lambda p, c, *a: unified_step(model, p, c, *a, prefix_blocks=1,
                                           **extra)
        args = (i32(1, 16), i32(1, 16), i32(1, M), i32(1), i32(1, 16), i32(1),
                key, f32(1), i32(1), f32(1))
    else:
        fn = lambda p, c, *a: multi_decode_step(
            model, p, c, *a, block_size=BS)
        args = (i32(B), i32(B), i32(B, M), i32(B), i32(B), key, f32(B),
                i32(B), f32(B))
    return jax.jit(fn).lower(params, cache, *args).as_text()


def _digest(family: str, program: str) -> str:
    return hashlib.sha256(
        _lowered(_toy(family), program).encode()).hexdigest()


@pytest.mark.parametrize("family,program", sorted(PARENT_HLO))
def test_a_model_without_a_state_lowers_to_the_program_it_had(family, program):
    assert _digest(family, program) == PARENT_HLO[(family, program)]


# the delta-rule toy (hybrid_linear_tiny.TINY) on the parent of the PR that
# gave the model class a second recurrence (b0b93f4): that PR's switches
# (multipliers, the optional gate, tied embeddings, the router's bias) add
# nothing to the programs of the model that was there
PARENT_DELTA_RULE_HLO = {
    "decode": "482b7f64469a625775672dfa07627ce22931f1f2eb1bb4bf50d6a4aeaf925994",
    "prefill": "bd8afad81fb2f2467009d0e9fe106c4754741e1c4d9d3ed2c902bcd0473dc201",
}


@pytest.mark.parametrize("program", sorted(PARENT_DELTA_RULE_HLO))
def test_the_delta_rule_model_lowers_to_the_program_it_had(program):
    from hybrid_linear_tiny import build

    model, _ = build()
    extra = ({"seq_slots": jnp.zeros((1,), jnp.int32)}
             if program == "prefill" else {})
    assert hashlib.sha256(_lowered(model, program, **extra).encode()
                          ).hexdigest() == PARENT_DELTA_RULE_HLO[program]


# the state-space toy (granite_hybrid_tiny.TINY) and, again, the delta-rule
# toy on the parent of the PR that gave the model class its third recurrence
# and a feed-forward without experts (2fb8531): neither adds an operation to
# the programs of the two recurrences that were there
PARENT_SSD_HLO = {
    "decode": "22a1da34756c9c38d0d41463c213721615e722e2f5235b629cbdc4d80d812ba2",
    "prefill": "a52bbe7b19323615fb5c6d1f813f71a0136e4bfcabb9ce598bcc49c7332e1dd5",
}


@pytest.mark.parametrize("program", sorted(PARENT_SSD_HLO))
def test_the_state_space_model_lowers_to_the_program_it_had(program):
    from granite_hybrid_tiny import build

    model, _ = build()
    extra = ({"seq_slots": jnp.zeros((1,), jnp.int32)}
             if program == "prefill" else {})
    assert hashlib.sha256(_lowered(model, program, **extra).encode()
                          ).hexdigest() == PARENT_SSD_HLO[program]


def test_the_selective_model_lowers_to_one_scan_a_run_of_layers():
    """m x7 | A | m x6: two scans more than a model whose layers are one (a
    run of one layer is a scan of one step); on the CPU a chunk's recurrence
    is one more, ``selective_scan``'s over its tokens."""
    from jamba_tiny import build

    model, _ = build()
    slot = jnp.zeros((1,), jnp.int32)
    for program, extra in (("decode", {}), ("prefill", {"seq_slots": slot})):
        text = _lowered(model, program, **extra)
        assert text.count("stablehlo.while") == _lowered(
            _toy("llama"), program).count("stablehlo.while") + 2 + (
                program == "prefill")       # ... and the chunk's token scan


def test_the_hybrid_model_lowers_to_one_scan_a_run_of_layers():
    """G | L L L | G | L L L: four scans in a decode step; a 16-token chunk
    is one piece of the recurrence, so a prefill has the same four.  The chunk is told its
    row's slot and the decode is not (its rows are the slot array)."""
    from hybrid_linear_tiny import build

    model, _ = build()
    assert [(r.kind, r.count) for r in model.runs] == [
        ("gqa", 1), ("linear", 3), ("gqa", 1), ("linear", 3)]
    loops = lambda m, program, **kw: _lowered(m, program, **kw).count(
        "stablehlo.while")
    # three scans more than a model whose layers are one scan
    assert loops(model, "decode") == loops(_toy("llama"), "decode") + 3
    slot = jnp.zeros((1,), jnp.int32)
    assert (loops(model, "prefill", seq_slots=slot)
            == loops(_toy("llama"), "prefill") + 3)
    with pytest.raises(ValueError, match="names its rows' slots"):
        _lowered(model, "prefill")                      # 1 row, 4 slots


if __name__ == "__main__":
    print({k: _digest(*k) for k in sorted(PARENT_HLO)})
