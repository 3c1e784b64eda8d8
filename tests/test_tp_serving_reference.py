"""``--tp 4`` serving against the plain float32 reference, on the CPU.

A tiny Mistral-shaped model (kv heads divide by four) is served by an
``EngineCore`` on a ``(1, 4)`` mesh of virtual devices — chunked prefill,
then decode through the sharded cache, one request at a time and three
together — and the top-8 log-probabilities of every generated position are
set against ``cellbench/reference/dense_gqa.py`` run teacher-forced on the
engine's own tokens (what ``cellbench/check.py`` does on the chip at the
published widths, PR 27).

The tolerance.  Both sides compute in float32, the reference at the highest
matmul precision: they differ by the order of the sums alone (four partial
sums and an all-reduce against one dot; a paged cache against one pass),
which measured 2.4e-6 at worst here.  ``ABS_TOL`` is twenty times that, and
the least of the faults it is there for moves a pair by 0.019 (bf16 weights;
a lost partial sum 2.7, exchanged heads 4.2), four hundred times more.  The
controls hand the *reference* the faulty weights, which is the same
discrepancy seen from the other side: weights rounded to bf16 (a lower
precision than the configuration states), one device's partial sum of the
attention output missing (a dropped all-reduce), two kv heads that live on
different shards exchanged (a head-to-shard mix-up)."""

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import spec
from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.utils.mesh import build_mesh

ABS_TOL = 5e-5
TOP, N_NEW, CHUNK = 8, 6, 16
PROMPT_LENS = (5, 21, 50)       # one chunk, two, four
HF = {"architectures": ["MistralForCausalLM"], "vocab_size": 256,
      "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 3,
      "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
      "max_position_embeddings": 512, "tie_word_embeddings": False}


@functools.cache
def reference_forward():
    ref = spec.load_module(Path(__file__).resolve().parent.parent,
                           "reference", "dense_gqa")
    return jax.jit(ref.make_forward(HF))


def ask(core, prompts):
    """Submit the prompts together, step the engine dry; per prompt the
    generated tokens and, per position, {token id: log-probability}."""
    outs = [[] for _ in prompts]
    for i, p in enumerate(prompts):
        core.submit(EngineRequest(
            f"r{i}-{len(prompts)}", list(p),
            SamplingOptions(temperature=0.0, logprobs=True, top_logprobs=TOP),
            StopConditions(max_tokens=N_NEW), outs[i].append))
    while core.step():
        pass
    answers = []
    for p, got in zip(prompts, outs):
        tokens = [t for o in got for t in o.token_ids]
        top = [dict(o.top_logprobs[0]) for o in got]
        assert len(tokens) == N_NEW and all(len(t) == TOP for t in top)
        answers.append({"prompt": list(p), "tokens": tokens, "top": top})
    return answers


@pytest.fixture(scope="module")
def served():
    cfg = ModelConfig.from_hf_config(HF, dtype="float32")
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.key(27))
    mesh = build_mesh((1, 4), devices=jax.devices()[:4])
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=8, num_blocks=64,
        prefill_chunk_tokens=CHUNK), mesh=mesh, eos_token_ids=[])
    assert core.metrics()["mesh_tp"] == 4 and core.metrics()["mesh_devices"] == 4
    rng = np.random.RandomState(27)
    prompts = [rng.randint(1, 256, size=n).tolist() for n in PROMPT_LENS]
    answers = {"alone": [ask(core, [p])[0] for p in prompts],
               "batched": ask(core, prompts)}
    # every weight the engine serves is split four ways where the rule says
    wq = core.params["layers"]["wq"]
    assert {s.data.shape for s in wq.addressable_shards} == {(3, 128, 32)}
    assert core.cache.addressable_shards[0].data.shape[-1] == 4 * 16 // 4
    return jax.device_get(core.params), answers


def deltas(forward, params, answer, positions):
    seq = answer["prompt"] + answer["tokens"]
    tokens = np.zeros(64, np.int32)
    tokens[: len(seq)] = seq
    at = np.arange(len(answer["prompt"]) - 1, len(seq) - 1, dtype=np.int32)
    ref = np.asarray(forward(params, tokens, at))
    return [abs(lp - float(ref[pos][tid]))
            for pos in positions for tid, lp in answer["top"][pos].items()]


# position 0 is sampled by the last prefill chunk, the rest by decode steps
# that read everything before them out of the paged, sharded cache
PHASES = {"prefill": [0], "decode": list(range(1, N_NEW))}


@pytest.mark.parametrize("phase", sorted(PHASES))
@pytest.mark.parametrize("mode", ["alone", "batched"])
def test_tp4_serving_agrees_with_the_float32_reference(served, mode, phase):
    params, answers = served
    forward = reference_forward()
    worst = max(d for a in answers[mode]
                for d in deltas(forward, params, a, PHASES[phase]))
    assert worst <= ABS_TOL, worst


def test_alone_and_batched_sample_the_same_tokens(served):
    _, answers = served
    for a, b in zip(answers["alone"], answers["batched"]):
        assert a["tokens"] == b["tokens"]


def _bf16_weights(p):
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)


def _lost_partial_sum(p):
    """Device 3's rows of ``wo`` contribute nothing: the all-reduce after
    the attention output summed three partial results, not four."""
    wo = np.array(p["layers"]["wo"])
    wo[:, 3 * wo.shape[1] // 4:, :] = 0.0
    return {**p, "layers": {**p["layers"], "wo": wo}}


def _heads_on_the_wrong_shard(p):
    """kv heads 1 and 2 (devices 1 and 2) exchanged in ``wk`` and ``wv``."""
    def swap(w):
        w = np.array(w).reshape(w.shape[0], w.shape[1], 4, 16)
        w[:, :, [1, 2]] = w[:, :, [2, 1]]
        return w.reshape(w.shape[0], w.shape[1], 64)
    layers = {**p["layers"], "wk": swap(p["layers"]["wk"]),
              "wv": swap(p["layers"]["wv"])}
    return {**p, "layers": layers}


@pytest.mark.parametrize("fault", [_bf16_weights, _lost_partial_sum,
                                   _heads_on_the_wrong_shard],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_tolerance_rejects(served, fault):
    params, answers = served
    forward = reference_forward()
    worst = max(d for a in answers["batched"]
                for d in deltas(forward, fault(params), a, range(N_NEW)))
    assert worst > 20 * ABS_TOL, worst

