"""What the hybrid-linear tests share: a tiny ``solar_open2`` configuration
(two periods ``G L L L``, float32), the model on seeded weights, the plain
reference of the benchmark (cellbench/reference/hybrid_linear.py) and an
engine around the model that records every request's tokens and top
log-probabilities.  No test lives here (ROADMAP R1 (11): files of <= 6
tests)."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)

ROOT = Path(__file__).resolve().parent.parent
BS, NB, SLOTS = 8, 64, 4
# float32 end to end: what is left between the program and the reference is
# the order of the sums (chunked against token by token, paged against dense)
ROUNDING = 2e-3


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "_hybrid_linear_reference",
        ROOT / "cellbench/reference/hybrid_linear.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load_reference()

TINY = dict(
    model_type="solar_open2", vocab_size=128, hidden_size=64,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, moe_intermediate_size=32,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    gqa_interval=3, gqa_layers=[0, 4], use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    first_k_dense_replace=0, tie_word_embeddings=False,
    n_routed_experts=2, n_shared_experts=1, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=1, rms_norm_eps=1e-5,
    max_position_embeddings=4096,
    expert_parallel={"chips": 4, "router_experts": 8, "first_expert": 2})


def build(cfg: dict = TINY, seed: int = 0, **kw):
    model = HybridLinearModel(
        HybridLinearConfig.from_hf_config(cfg, dtype="float32"), **kw)
    return model, model.init_params(jax.random.PRNGKey(seed))


def tokens_of(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(1, 128, n)]


def want(params, tokens, at, cfg: dict = TINY) -> np.ndarray:
    return np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(at)))


def engine(model, params, **kw):
    from dynamo_tpu.engine import EngineConfig, EngineCore

    conf = dict(max_batch_size=SLOTS, max_model_len=256, block_size=BS,
                num_blocks=NB, prefill_chunk_tokens=32)
    conf.update(kw)
    return EngineCore(model, params, EngineConfig(**conf), eos_token_ids=[])


def submit(core, name: str, prompt: list[int], max_tokens: int, got: dict,
           top: int = 5) -> None:
    """Greedy, with the top log-probabilities of every generated position
    gathered into ``got[name]`` = (tokens, [[(id, logprob), ...], ...])."""
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    got[name] = ([], [])

    def emit(o):
        got[name][0].extend(o.token_ids)
        got[name][1].extend(o.top_logprobs or [])

    core.submit(EngineRequest(
        request_id=name, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0, logprobs=True,
                                 top_logprobs=top),
        stops=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        emit=emit))


def drain(core) -> None:
    while core.step():
        pass


def worst_delta(params, prompt, answer, cfg: dict = TINY, want=want) -> float:
    """Teacher-forced: the largest |log-probability - reference's| over the
    top candidates of every generated position of one request (``want``:
    another toy's reference)."""
    tokens, tops = answer
    seq = list(prompt) + list(tokens)
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    ref_logp = want(params, seq, at, cfg)
    return max(abs(lp - ref_logp[i][tid])
               for i, cands in enumerate(tops) for tid, lp in cands)
