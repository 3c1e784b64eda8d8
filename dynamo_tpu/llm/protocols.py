"""Engine-agnostic internal request/response protocol.

The preprocessor turns OpenAI-level requests into a BackendInput (token ids
+ sampling + stop conditions); engines emit LLMEngineOutput deltas; the
backend detokenizes them into text deltas.

Reference parity: lib/llm/src/protocols/common/llm_backend.rs:1-126
(BackendInput, LLMEngineOutput, FinishReason) and protocols/common/
(SamplingOptions, StopConditions).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Optional


class FinishReason(str, enum.Enum):
    EOS = "eos"          # hit an end-of-sequence token
    STOP = "stop"        # hit a stop sequence / stop token
    LENGTH = "length"    # max_tokens or model context limit
    CANCELLED = "cancelled"
    ERROR = "error"

    def as_openai(self) -> str:
        """Map to OpenAI finish_reason strings."""
        if self in (FinishReason.EOS, FinishReason.STOP):
            return "stop"
        if self is FinishReason.LENGTH:
            return "length"
        return "stop" if self is FinishReason.CANCELLED else "error"


@dataclass
class SamplingOptions:
    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0
    # min-p nucleus floor (vLLM extension; ref protocols/common.rs:293):
    # drop candidates with prob < min_p * max_prob.  0 = disabled
    min_p: float = 0.0
    # OpenAI logit_bias: token id -> additive bias in [-100, 100]
    logit_bias: Optional[dict[int, float]] = None
    seed: Optional[int] = None
    # OpenAI penalties over generated tokens (engine/sampling.py applies
    # them by scatter-add on device; vLLM-compatible semantics)
    frequency_penalty: float = 0.0
    presence_penalty: float = 0.0
    # logprob reporting: chosen-token logprob and top-N alternatives
    logprobs: bool = False
    top_logprobs: int = 0
    # response_format JSON mode: grammar-constrained decoding (the engine
    # masks invalid-next-token logits inside the decode scan; engine/grammar.py)
    json_mode: bool = False
    # guided_choice (vLLM-compatible extension): the output is exactly one
    # of these strings — enforced by a choice-trie grammar in the same scan
    guided_choice: Optional[list[str]] = None
    # guided_regex (vLLM-compatible extension): the output fullmatches this
    # pattern (bounded regex subset compiled to a byte DFA)
    guided_regex: Optional[str] = None

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


@dataclass
class StopConditions:
    max_tokens: Optional[int] = None
    stop: list[str] = field(default_factory=list)          # stop strings (detok layer)
    stop_token_ids: list[int] = field(default_factory=list)
    ignore_eos: bool = False
    min_tokens: int = 0


@dataclass
class BackendInput:
    """What an engine consumes: tokens in, sampling+stop config."""

    token_ids: list[int] = field(default_factory=list)
    sampling: SamplingOptions = field(default_factory=SamplingOptions)
    stops: StopConditions = field(default_factory=StopConditions)
    model: str = ""
    annotations: dict[str, Any] = field(default_factory=dict)


@dataclass
class LLMEngineOutput:
    """A streamed engine delta: newly generated token ids (usually one)."""

    token_ids: list[int] = field(default_factory=list)
    finish_reason: Optional[FinishReason] = None
    # engine-side bookkeeping surfaced for metrics/tests
    cached_tokens: int = 0      # prefix-cache hit length for this request
    # filled by the detokenizing backend:
    text: Optional[str] = None
    # per-token logprob data (aligned with token_ids), when requested:
    logprobs: Optional[list[float]] = None
    # per-token top-N candidates as (token_id, logprob) pairs
    top_logprobs: Optional[list[list[tuple]]] = None
    # display-form logprobs (token strings + bytes), filled by the Backend:
    # [{token, logprob, bytes, top_logprobs: [{token, logprob, bytes}]}]
    logprob_content: Optional[list[dict]] = None
    # when the engine thread emitted this output (time.perf_counter of the
    # emitting process; 0.0 = not stamped), so that the writer of its
    # chunk can say how long it waited for the event loop.  Deliberately
    # no dataclass field: it means nothing in another process, and stays
    # off the wire (runtime/serde.py encodes fields) and out of equality
    emitted_at = 0.0

    def __post_init__(self):
        # tolerate wire-decoded plain strings (runtime/serde.py)
        if isinstance(self.finish_reason, str):
            self.finish_reason = FinishReason(self.finish_reason)

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None
