"""The Jamba toy (jamba_tiny.TINY) through ``EngineCore``'s default path and
through ``dynamo-tpu run``'s own loader: chunked prefill and decode against
the reference with the counters of the third recurrence, a freed slot taken
by a new request, and a published checkpoint directory served by the class
the registry names."""

import jax.numpy as jnp
import numpy as np
import pytest

from hybrid_linear_tiny import drain, engine, submit, tokens_of
from jamba_tiny import ROUNDING, TINY, build, worst_delta


def test_engine_serves_it_in_chunks_then_decodes_against_the_reference():
    """Two requests, one of three chunks (75 tokens, chunk 32): every
    generated position's top log-probabilities against the reference's full
    forward, and the counters — the three of ``STATE_COUNT_KEYS`` counted
    for the third recurrence, the experts' at zero."""
    model, params = build()
    core = engine(model, params)
    long, short = tokens_of(75, 1), tokens_of(20, 2)
    got: dict = {}
    submit(core, "long", long, 6, got)
    submit(core, "short", short, 10, got)
    drain(core)
    assert len(got["long"][0]) == 6 and len(got["short"][0]) == 10
    assert worst_delta(params, long, got["long"]) < ROUNDING
    assert worst_delta(params, short, got["short"]) < ROUNDING
    m = core.metrics()
    assert m["prefill_dispatches_total"] == 3 + 1
    run = 75 + 20 + m["decode_rows_dispatched_total"]
    assert m["state_tokens_total"] == 13 * run
    assert m["state_resets_total"] == 2
    assert m["state_position_mismatches_total"] == 0
    assert m["moe_router_picks_total"] == m["moe_experts_touched_total"] == 0
    assert (m["state_layers"], m["cache_layers"]) == (13, 1)
    assert m["kv_bytes_per_token"] == 1 * 2 * 1 * 16 * 4      # 1 layer, K+V
    assert m["state_bytes_per_slot"] == 13 * (16 * 128 * 4 + 3 * 128 * 4)
    assert m["state_update_kernel"] == 0 and m["prefix_reuse"] == 0
    assert m["ahead_dispatches_total"] > 0


def test_a_freed_slot_taken_by_a_new_request_starts_from_zero():
    """One slot: the second request sits where the first sat, over the
    first's left-over state, tail and K/V, and answers as on a fresh engine
    (a reset at position 0)."""
    model, params = build()
    first, second = tokens_of(50, 5), tokens_of(33, 6)
    used: dict = {}
    core = engine(model, params, max_batch_size=1)
    submit(core, "first", first, 5, used)
    drain(core)
    assert float(jnp.abs(core.cache["state"]).max()) > 0     # left behind
    submit(core, "second", second, 5, used)
    drain(core)
    fresh: dict = {}
    core2 = engine(model, params, max_batch_size=1)
    submit(core2, "second", second, 5, fresh)
    drain(core2)
    assert used["second"][0] == fresh["second"][0]
    assert worst_delta(params, second, used["second"]) < ROUNDING
    assert core.metrics()["state_resets_total"] == 2


def test_a_bf16_state_is_served_and_is_another_model():
    """The check's negative control: the state stored in bf16 between
    dispatches runs (the XLA forms: the kernels take float32) and moves the
    answer beyond float32's rounding over a few dozen steps."""
    model, params = build(state_dtype=jnp.bfloat16)
    core = engine(model, params)
    assert core.cache["state"].dtype == jnp.bfloat16
    prompt = tokens_of(40, 9)
    got: dict = {}
    submit(core, "r", prompt, 24, got)
    drain(core)
    assert worst_delta(params, prompt, got["r"]) > 10 * ROUNDING


def test_a_published_checkpoint_directory_is_served_by_the_hybrid_class(
        tmp_path):
    """``dynamo-tpu run --model-path <dir>``'s loader on a ``jamba``
    directory written by ``transformers``: the class the registry names, the
    published tensors under the program's names, and the same
    log-probabilities as the published model through the engine."""
    torch = pytest.importorskip("torch")
    from transformers import JambaConfig, JambaForCausalLM

    from dynamo_tpu import cli
    from dynamo_tpu.models.hybrid_linear import HybridLinearModel

    torch.manual_seed(3)
    published = JambaForCausalLM(JambaConfig(
        **{k: v for k, v in TINY.items() if k != "model_type"},
        attn_implementation="eager")).eval().float()
    published.save_pretrained(tmp_path, safe_serialization=True)
    model, params, quantized = cli._load_any_checkpoint(str(tmp_path), "float32")
    assert isinstance(model, HybridLinearModel) and not quantized
    assert model.config.recurrence == "selective"
    prompt = tokens_of(45, 11)
    got: dict = {}
    core = engine(model, params)
    submit(core, "r", prompt, 4, got)
    drain(core)
    tokens, tops = got["r"]
    seq = prompt + list(tokens)
    with torch.no_grad():
        logp = torch.log_softmax(
            published(torch.tensor([seq])).logits[0], dim=-1).numpy()
    worst = max(abs(lp - logp[len(prompt) - 1 + i][tid])
                for i, cands in enumerate(tops) for tid, lp in cands)
    assert worst < ROUNDING
