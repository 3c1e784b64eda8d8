"""Engine configuration: batching, cache sizing, bucketing, sharding."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional


def default_buckets(max_len: int) -> list[int]:
    """Powers of two up to max_len (prefill padding buckets)."""
    out = []
    b = 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


@dataclass
class EngineConfig:
    # batching
    max_batch_size: int = 8           # decode slots (static shape)
    max_model_len: int = 2048
    # chunked prefill: max prompt tokens computed per prefill dispatch
    # (0 = whole remainder in one step).  Bounding the chunk keeps decode
    # ITL flat while long prompts prefill — the scheduler alternates one
    # prefill chunk with one decode step when both have work (the
    # reference gets this from vLLM's chunked-prefill scheduler; ours is
    # native).  Rounded down to a block multiple so resumed chunks stay
    # block-aligned for the prefill fast path.
    prefill_chunk_tokens: int = 0
    # token-budget ragged prefill: pack the prefill chunks of SEVERAL
    # pending requests into one flat-token-axis dispatch of at most this
    # many tokens (each request's chunk occupies a block-aligned span; the
    # flat axis is bucketed via bucket_for so executables stay O(log)).
    # Converts a backlog of N short prompts from N device round-trips to
    # ~ceil(total_tokens / budget) dispatches.  0 = legacy one-request-
    # per-dispatch prefill.  Rounded down to a block multiple; capped at
    # max_model_len (the largest prefill bucket).
    prefill_token_budget: int = 0
    # unified mixed prefill+decode dispatch: when BOTH phases have work,
    # run ONE token-budget ragged step per turn — decode rows (1 token
    # each) lead the flat axis, waiting prefill chunks pack into the
    # remaining prefill_token_budget.  Replaces the chunked-prefill
    # alternation (one device round-trip per phase switch) with a single
    # dispatch per turn; decode-only turns keep the decode step and
    # prefill-only turns the ragged batch.  Requires a model with the
    # ragged forward path; prefill_token_budget defaults on when unset.
    # Default off until parity-gated (tests/test_unified_dispatch.py
    # pins seeded-stream parity vs the legacy paths).
    unified_token_dispatch: bool = False
    # refused (ValueError below): dispatch-ahead (EngineCore._settle) is
    # the engine's one overlap of host and device and has no option.  The
    # name stays while cellbench/server.py passes it (ROADMAP D12).
    lookahead_dispatch: bool = False
    # prompt-lookup speculative decoding (engine/spec.py): propose up to
    # spec_tokens continuation tokens by n-gram match against the sequence
    # itself and verify them in ONE dispatch.  Greedy-exact; engages only
    # for dispatches where every active request is plain greedy (no
    # penalties/logprobs/bias/min_p/JSON mode).  0 = off.
    spec_tokens: int = 0
    spec_ngram: int = 3
    # draft-model speculation (engine/draft.py): block count of the
    # draft's own paged cache.  0 = same count as the target's — shrink
    # it on HBM-tight deployments (the draft cache costs
    # L_draft/L_target of the target cache at equal counts).
    draft_num_blocks: int = 0
    # sequence-parallel (ring attention) prefill: prompts at least this
    # long (with no cached prefix) prefill in ONE dispatch with the
    # sequence sharded over the mesh's "data" axis — context parallelism
    # for prompts beyond a single chip's comfort.  0 = disabled; requires
    # an engine mesh whose "data" axis is > 1.
    sp_prefill_threshold: int = 0
    # paged cache
    block_size: int = 16
    num_blocks: int = 512             # cache blocks in HBM
    num_host_blocks: int = 0          # host-RAM offload tier (0 = disabled)
    # async-offload HBM backpressure: total device blocks that may sit in
    # queued gather snapshots awaiting the device→host readback.  A batch
    # that would push the outstanding count past this budget stores
    # synchronously instead (each queued snapshot pins its blocks' HBM —
    # a burst of large evictions must not pin hundreds of MB)
    offload_inflight_blocks: int = 256
    # persistent prefix-cache tier (llm/kv/persist.py): directory for the
    # content-addressed block store.  None/"" = disabled (the default).
    # Requires num_host_blocks > 0 — spill and restore both stage through
    # the host pool.  Blocks published to the host pool spill here
    # asynchronously; host-pool misses on admission fall through to this
    # tier, so a restart (same dir) or a replicated index re-enters warm
    # prefixes as cached_tokens.
    kv_persist_dir: Optional[str] = None
    # size cap for the persistent store (LRU by last-touch at block-group
    # file granularity); 0 = unbounded
    kv_persist_max_bytes: int = 0
    # TTL for persisted block groups since last touch; 0 = no expiry
    kv_persist_ttl_s: float = 0.0
    # KV cache dtype: None = model dtype; "int8" = quantized cache with
    # per-token-per-head scales (ops/kv_quant.py) — half the KV HBM
    # footprint and decode-step KV traffic
    cache_dtype: Optional[str] = None
    enable_prefix_reuse: bool = True
    # force exact lax.top_k candidate selection in the sampler (the default
    # approx_max_k path is exact for greedy and ~0.95-recall for the deep
    # tail; requests with top_k > 64 switch to exact automatically)
    exact_sampling: bool = False
    # prefill
    prefill_buckets: list[int] = field(default_factory=list)
    # sharding: data/model axis sizes; 1,1 = single chip
    mesh_shape: tuple[int, int] = (1, 1)
    # where ``POST /debug/profile`` on the HTTP service writes its
    # jax.profiler captures (CLI: --profile-dir); the engine itself starts
    # no capture.  profile_steps is inert since PR 25 (captures are asked
    # for in seconds, when the server is warm): the field and its flag stay
    # only because cellbench/server.py still passes them.
    profile_dir: Optional[str] = None
    profile_steps: int = 8
    # rng
    seed: int = 0

    def __post_init__(self):
        if not self.prefill_buckets:
            self.prefill_buckets = default_buckets(self.max_model_len)
        self.prefill_buckets = sorted(self.prefill_buckets)
        if self.prefill_chunk_tokens:
            # block-align the chunk so every resumed chunk starts on a block
            # boundary (required by the prefill fast path)
            self.prefill_chunk_tokens = max(
                self.block_size,
                self.prefill_chunk_tokens // self.block_size * self.block_size,
            )
        if self.lookahead_dispatch:
            raise ValueError(
                "lookahead_dispatch is refused: dispatch-ahead hides the host "
                "round trip under the next device program by default and "
                "has no option (docs/engine_scheduling.md)")
        if self.unified_token_dispatch and not self.prefill_token_budget:
            # the unified scheduler packs under prefill_token_budget; a
            # bare --unified-token-dispatch gets a sensible default
            # rather than silently staying on the legacy paths
            self.prefill_token_budget = min(1024, self.max_model_len)
        if self.prefill_token_budget:
            # block-align (spans in the packed axis are block multiples)
            # and cap at the largest prefill bucket — bucket_for pads the
            # flat axis, so a budget past max_model_len could never fill
            self.prefill_token_budget = max(
                self.block_size,
                self.prefill_token_budget // self.block_size * self.block_size,
            )
            self.prefill_token_budget = min(
                self.prefill_token_budget, self.max_model_len
            )

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_model_len // self.block_size)

    def bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"sequence length {n} exceeds max_model_len {self.max_model_len}")
