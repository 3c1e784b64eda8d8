"""Per-request engine state machine."""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Callable, Optional

from dynamo_tpu.engine.grammar import INIT_STATE
from dynamo_tpu.llm.protocols import (
    FinishReason,
    LLMEngineOutput,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.tokens import TokenBlockSequence


class RequestState(enum.Enum):
    WAITING = "waiting"    # queued, no slot yet
    PREFILL = "prefill"    # slot assigned, prompt not fully computed
    REMOTE_PREFILL = "remote_prefill"  # slot+blocks assigned; KV arrives from a prefill worker
    RUNNING = "running"    # decoding
    FINISHED = "finished"


@dataclass
class EngineRequest:
    request_id: str
    prompt: list[int]
    sampling: SamplingOptions = field(default_factory=SamplingOptions)
    stops: StopConditions = field(default_factory=StopConditions)
    # called from the engine thread with each LLMEngineOutput delta
    emit: Callable[[LLMEngineOutput], None] = lambda out: None

    # --- disaggregation flags (ref vllm patch remote_prefill.py:
    # RemotePrefillParams.is_remote_prefill / is_remote_decode) ---
    # decode side: blocks are allocated up front and the request stalls in
    # REMOTE_PREFILL until a prefill worker writes KV and notifies
    remote_prefill: bool = False
    # prefill side: stop after the prefill step + first sampled token, keep
    # blocks held (not released) until the worker has transferred them out
    remote_decode: bool = False
    # called on the engine thread right after blocks are allocated (decode
    # side uses this to learn the block ids to hand to the prefill worker)
    on_allocated: Optional[Callable[["EngineRequest"], None]] = None

    state: RequestState = RequestState.WAITING
    seq: Optional[TokenBlockSequence] = None  # prompt + generated tokens
    block_ids: list[int] = field(default_factory=list)
    cached_tokens: int = 0     # prefix-cache hit (KV already resident)
    computed_tokens: int = 0   # prompt tokens whose KV is computed
    # prompt tokens whose blocks were already offered to block_manager
    # .commit — the chunked-prefill watermark (each chunk commits only the
    # blocks it completed; re-offering every earlier block per chunk made
    # an L-block prompt pay O(L^2) commit calls)
    committed_upto: int = 0
    # prompt tokens [computed_tokens, wait_upto) live in blocks another
    # request is prefilling right now (joined via the reserved-block
    # registry): this request absorbs them as the owner commits instead of
    # recomputing, and takes over if the owner aborts
    wait_upto: int = 0
    # (seq_hash, block_id) reservations THIS request owns; unresolved ones
    # are dropped on finish so joiners can take over
    reserved_pairs: list = field(default_factory=list)
    generated: int = 0
    # JSON-mode grammar automaton state: (dfa_state, depth, bit-stack) —
    # advanced host-side per appended token, mirrored on device in-scan
    gstate: tuple = (INIT_STATE, 0, 0)
    slot: int = -1
    admit_seq: int = -1        # position in the order of admission
    finish_reason: Optional[FinishReason] = None
    abort_requested: bool = False
    # dtspan trace context (trace_id, span_id) — the engine thread has
    # no ambient contextvar, so spans it records for this request pass
    # this pair as parent= explicitly (obs/tracing.py)
    trace: Optional[tuple] = None
    # queue-wait measurement: submit() stamps submitted_at
    # (perf_counter); _admit computes queue_wait_s at slot assignment
    # and the async engine surfaces it to the HTTP histogram
    submitted_at: float = 0.0
    queue_wait_s: Optional[float] = None
    # the boundaries a request crosses on its way to a first token, on
    # submitted_at's clock (perf_counter: on Linux the clock of
    # time.monotonic_ns, which dtspan and the profiler's t_mono_ns use).
    # queue_wait_s + (first_issue_at - admitted_at) + (first_token_at -
    # first_issue_at) is its engine TTFT; 0.0 = not crossed yet
    admitted_at: float = 0.0       # took a slot (_admit)
    first_issue_at: float = 0.0    # first dispatch that carried it went out
    first_token_at: float = 0.0    # first token emitted
    # step_timeline.busy_steps_total at those two moments: the busy steps
    # [first_issue_step, first_token_step] prefilled it (the ``step`` of
    # the profiler's dyn.<phase> events)
    first_issue_step: int = -1
    first_token_step: int = -1
    prefill_chunks: int = 0        # prefill dispatches that carried it

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def total_tokens(self) -> int:
        return self.seq.total_tokens if self.seq else self.prompt_len
