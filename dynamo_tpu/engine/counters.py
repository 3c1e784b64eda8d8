"""Process-global prefill-batching counters.

Same dependency-free idiom as ``dynamo_tpu/fault/counters.py``: the
engine layer records, the llm layer (http/metrics.py render) and the
benchmarks read — no import cycles.  The HTTP metrics endpoint exposes:

    dynamo_tpu_engine_prefill_dispatches_total     counter
    dynamo_tpu_engine_prefill_tokens_total         counter
    dynamo_tpu_engine_prefill_batch_occupancy      gauge (rows/dispatch)
    dynamo_tpu_engine_prefill_budget_utilization   gauge (used/offered)
    dynamo_tpu_engine_prefill_ready_rows_total     counter (requests ready
                                                   to prefill, summed at
                                                   every prefill dispatch:
                                                   over the dispatches, the
                                                   backlog a served request
                                                   stood in)
    dynamo_tpu_engine_unified_dispatches_total     counter
    dynamo_tpu_engine_unified_decode_rows_total    counter
    dynamo_tpu_engine_unified_prefill_tokens_total counter
    dynamo_tpu_engine_unified_budget_utilization   gauge (used/offered)

The ``unified_*`` family counts the mixed prefill+decode dispatches of
the unified token-budget scheduler (engine/core.py ``_run_unified``):
how many turns collapsed the legacy two-dispatch interleave into one,
how many decode rows and prefill tokens shared each flat axis, and how
full the offered axis budget ran.
"""

from __future__ import annotations

__all__ = ["PrefillCounters", "counters", "PersistCounters", "persist_counters",
           "KvStreamCounters", "kv_stream_counters",
           "KvShardCounters", "kv_shard_counters",
           "RequestCounters", "request_counters"]


class PrefillCounters:
    def __init__(self) -> None:
        self.reset()

    def record(self, rows: int, tokens: int, budget: int = 0) -> None:
        """One prefill dispatch: ``rows`` sequences packed, ``tokens``
        prompt tokens computed.  ``budget`` is the token budget offered
        (0 for legacy one-request / seq-parallel dispatches — those don't
        count toward budget utilization)."""
        self.dispatches_total += 1
        self.rows_total += rows
        self.tokens_total += tokens
        if budget > 0:
            self.budget_offered_total += budget
            self.budget_used_total += tokens

    def record_ready(self, rows: int) -> None:
        """A prefill dispatch went out with ``rows`` requests standing
        ready for one (itself included)."""
        self.ready_rows_total += rows

    def record_unified(self, decode_rows: int, prefill_tokens: int,
                       budget: int) -> None:
        """One unified mixed dispatch: ``decode_rows`` 1-token decode
        rows plus ``prefill_tokens`` prompt tokens packed on one flat
        axis, under an offered budget of ``budget`` tokens."""
        self.unified_dispatches_total += 1
        self.unified_decode_rows_total += decode_rows
        self.unified_prefill_tokens_total += prefill_tokens
        self.unified_budget_offered_total += budget
        self.unified_budget_used_total += decode_rows + prefill_tokens

    @property
    def unified_budget_utilization(self) -> float:
        """(decode rows + prefill tokens) / budget offered over unified
        dispatches."""
        if not self.unified_budget_offered_total:
            return 0.0
        return (self.unified_budget_used_total
                / self.unified_budget_offered_total)

    @property
    def batch_occupancy(self) -> float:
        """Mean sequences per prefill dispatch (lifetime)."""
        if not self.dispatches_total:
            return 0.0
        return self.rows_total / self.dispatches_total

    @property
    def budget_utilization(self) -> float:
        """Tokens packed / budget offered over batched dispatches."""
        if not self.budget_offered_total:
            return 0.0
        return self.budget_used_total / self.budget_offered_total

    def reset(self) -> None:
        """Test isolation hook — the counters are process-global."""
        self.dispatches_total = 0
        self.rows_total = 0
        self.tokens_total = 0
        self.budget_offered_total = 0
        self.budget_used_total = 0
        self.ready_rows_total = 0
        self.unified_dispatches_total = 0
        self.unified_decode_rows_total = 0
        self.unified_prefill_tokens_total = 0
        self.unified_budget_offered_total = 0
        self.unified_budget_used_total = 0


counters = PrefillCounters()


class PersistCounters:
    """Persistent prefix-cache tier (llm/kv/persist.py) counters.

        dynamo_tpu_engine_persist_hits_total            counter (blocks)
        dynamo_tpu_engine_persist_misses_total          counter (lookups
                                                        that restored
                                                        nothing)
        dynamo_tpu_engine_persist_restored_tokens_total counter
        dynamo_tpu_engine_persist_spill_bytes_total     counter
        dynamo_tpu_engine_persist_resident_bytes        gauge

    The store records spill volume and residency; the engine's restore
    path records hits/misses/restored tokens at commit time, so a match
    that failed to land on device never counts as a hit.
    """

    def __init__(self) -> None:
        self.reset()

    def record_restore(self, blocks: int, tokens: int) -> None:
        self.hits_total += blocks
        self.restored_tokens_total += tokens

    def record_miss(self) -> None:
        self.misses_total += 1

    def record_spill(self, nbytes: int) -> None:
        self.spill_bytes_total += nbytes

    def set_resident(self, nbytes: int) -> None:
        self.resident_bytes = nbytes

    def reset(self) -> None:
        """Test isolation hook — the counters are process-global."""
        self.hits_total = 0
        self.misses_total = 0
        self.restored_tokens_total = 0
        self.spill_bytes_total = 0
        self.resident_bytes = 0


persist_counters = PersistCounters()


class KvStreamCounters:
    """Streamed KV handoff (llm/kv/stream.py) counters.

        dynamo_tpu_kv_stream_sessions_total     counter (STREAM_BEGINs sent)
        dynamo_tpu_kv_stream_layers_sent_total  counter (WRITE_LAYER frames)
        dynamo_tpu_kv_stream_bytes_total        counter (layer payload bytes)
        dynamo_tpu_kv_stream_fallbacks_total    counter (sessions that fell
                                                back to the whole-cache push)
        dynamo_tpu_kv_stream_overlap_ratio      gauge

    ``overlap_ratio`` is transfer seconds HIDDEN under prefill compute
    (frames sent while later chunks were still computing) over total
    streamed transfer seconds — 1.0 means the wire was entirely paid
    for by compute, 0.0 means the stream degenerated to the blocking
    schedule (e.g. single-chunk prefills).
    """

    def __init__(self) -> None:
        self.reset()

    def record_session(self) -> None:
        self.sessions_total += 1

    def record_layer(self, nbytes: int, seconds: float,
                     hidden: bool) -> None:
        """One WRITE_LAYER frame acked: ``hidden`` marks frames sent
        while the producer's prefill was still computing."""
        self.layers_sent_total += 1
        self.bytes_total += nbytes
        self.transfer_seconds_total += seconds
        if hidden:
            self.hidden_seconds_total += seconds

    def record_fallback(self) -> None:
        self.fallbacks_total += 1

    @property
    def overlap_ratio(self) -> float:
        if self.transfer_seconds_total <= 0:
            return 0.0
        return self.hidden_seconds_total / self.transfer_seconds_total

    def reset(self) -> None:
        """Test isolation hook — the counters are process-global."""
        self.sessions_total = 0
        self.layers_sent_total = 0
        self.bytes_total = 0
        self.fallbacks_total = 0
        self.transfer_seconds_total = 0.0
        self.hidden_seconds_total = 0.0


kv_stream_counters = KvStreamCounters()


class KvShardCounters:
    """Sharded control plane (llm/kv_router/shards/) counters.

        dynamo_tpu_kv_shard_scatters_total        counter (gather rounds)
        dynamo_tpu_kv_shard_gather_partial_total  counter (rounds where a
                                                  shard missed its deadline
                                                  or answered stale)
        dynamo_tpu_kv_shard_fanout_latency_ms     histogram (scatter issue
                                                  → last reply/deadline)
        dynamo_tpu_kv_shard_generation            gauge (current fence)
        dynamo_tpu_kv_shard_last_fan_out          gauge (shards in the
                                                  last scatter round)
        dynamo_tpu_kv_shard_index_blocks{shard=}  gauge (device blocks)
        dynamo_tpu_kv_shard_resident_keys{shard=} gauge (distinct keys,
                                                  both tiers)

    The fan-out histogram lives here (cumulative bucket counts over the
    fixed ladder below) rather than in http/metrics.py's Histogram so
    the router layer stays free of the HTTP module; the render side
    turns the buckets into Prometheus histogram lines.
    """

    FANOUT_BUCKETS_MS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                         25.0, 50.0, 100.0)

    def __init__(self) -> None:
        self.reset()

    def record_scatter(self, fanout_ms: float, fan_out: int = 0) -> None:
        """One scatter round completed (all replies in, or deadline)."""
        self.scatters_total += 1
        self.fanout_ms_sum += fanout_ms
        self.last_fan_out = fan_out
        for i, edge in enumerate(self.FANOUT_BUCKETS_MS):
            if fanout_ms <= edge:
                self.fanout_bucket_counts[i] += 1

    def record_partial_gather(self) -> None:
        self.gather_partial_total += 1

    def set_generation(self, generation: int) -> None:
        self.generation = generation

    def set_shard_size(self, shard_id: int, index_blocks: int,
                       resident_keys: int) -> None:
        self.index_blocks[shard_id] = index_blocks
        self.resident_keys[shard_id] = resident_keys

    @property
    def gather_partial_frac(self) -> float:
        if not self.scatters_total:
            return 0.0
        return self.gather_partial_total / self.scatters_total

    def reset(self) -> None:
        """Test isolation hook — the counters are process-global."""
        self.scatters_total = 0
        self.gather_partial_total = 0
        self.fanout_ms_sum = 0.0
        self.fanout_bucket_counts = [0] * len(self.FANOUT_BUCKETS_MS)
        self.last_fan_out = 0
        self.generation = 0
        self.index_blocks: dict[int, int] = {}
        self.resident_keys: dict[int, int] = {}


kv_shard_counters = KvShardCounters()


class RequestCounters:
    """What an operator asks of the decode path and of request endings,
    counted on the engine thread where it happens.

        dynamo_tpu_engine_decode_dispatches_total      counter (pure-decode
                                                       dispatches: burst or
                                                       speculative verify)
        dynamo_tpu_engine_decode_rows_dispatched_total counter (running rows
                                                       packed over them)
        dynamo_tpu_engine_requests_finished_total      counter (any reason)
        dynamo_tpu_engine_requests_cut_short_total     counter (ended with
                                                       ``length`` because
                                                       KV block space ran
                                                       out — not max_tokens,
                                                       not max_model_len)
        dynamo_tpu_engine_first_tokens_total           counter (requests that
                                                       emitted a token)
        dynamo_tpu_engine_first_token_seconds_total    counter (sum of first
                                                       emit - submit: TTFT
                                                       as the engine sees it)
        dynamo_tpu_engine_turn_wait_seconds_total      counter (of that, the
                                                       sum of first dispatch
                                                       that carried the
                                                       request - slot: in a
                                                       slot, nothing issued
                                                       for it yet)
        dynamo_tpu_engine_prefill_span_seconds_total   counter (and of first
                                                       emit - that dispatch:
                                                       its chunks, the turns
                                                       between them, the
                                                       readback; with the
                                                       queue wait the three
                                                       add up to the TTFT)
        dynamo_tpu_engine_ahead_dispatches_total       counter (decode
                                                       dispatches issued
                                                       with a dispatch in
                                                       flight: over decode
                                                       dispatches, how often
                                                       the host's round trip
                                                       is hidden)
        dynamo_tpu_engine_ahead_discards_total         counter (rows whose
                                                       ahead-sample a stop
                                                       found one dispatch
                                                       late threw away: the
                                                       mechanism's waste)
        dynamo_tpu_engine_pipeline_drains_total        counter (turns that
                                                       read back before they
                                                       could issue: why the
                                                       engagement is not 1)
        dynamo_tpu_engine_operand_buffers_total        counter (host->device
                                                       buffers the operands
                                                       of the dispatches
                                                       took: buffers put x
                                                       devices put to; over
                                                       prefill + decode
                                                       dispatches 2 x devices
                                                       under a mesh, the
                                                       number of arrays with
                                                       none)
        dynamo_tpu_engine_prompt_tokens_admitted_total counter (prompt tokens
                                                       of requests whose
                                                       prefill completed)
        dynamo_tpu_engine_prompt_tokens_cached_total   counter (of those, the
                                                       tokens served from
                                                       reused blocks: over
                                                       admitted, the prefix
                                                       cache's hit share)
        dynamo_tpu_engine_attn_context_tokens_total    counter (latent-
                                                       attention models:
                                                       cached positions the
                                                       decode rows dispatched
                                                       could see, summed)
        dynamo_tpu_engine_attn_selected_tokens_total   counter (of those, the
                                                       positions attended to:
                                                       min(context,
                                                       index_topk) a row, all
                                                       of them without an
                                                       indexer; over context,
                                                       how sparse attention
                                                       was)
        dynamo_tpu_engine_moe_router_picks_total       counter (experts the
                                                       router picked for the
                                                       real tokens of every
                                                       dispatch, counted on
                                                       the device: top-k a
                                                       token and expert layer)
        dynamo_tpu_engine_moe_held_picks_total         counter (of those, the
                                                       picks on the experts
                                                       this chip holds: the
                                                       rows its experts
                                                       computed)
        dynamo_tpu_engine_moe_expert_layer_calls_total counter (expert layers
                                                       run, one a layer and
                                                       dispatch)
        dynamo_tpu_engine_moe_experts_touched_total    counter (held experts
                                                       with at least one row,
                                                       summed over layers:
                                                       x an expert's bytes,
                                                       what the grouped
                                                       matmul streamed)
        dynamo_tpu_engine_state_tokens_total           counter (a model
                                                       with recurrent layers:
                                                       real tokens x such
                                                       layers advanced)
        dynamo_tpu_engine_state_resets_total           counter (sequences
                                                       started from a zero
                                                       state: position 0)
        dynamo_tpu_engine_state_position_mismatches_total  counter (rows that
                                                       went on at another
                                                       position than their
                                                       slot's state stood at:
                                                       0, the slot contract)
        dynamo_tpu_engine_loop_tokens_total            counter (tokens that
                                                       went out in a prefill
                                                       or decode dispatch)
        dynamo_tpu_engine_loop_passes_total            counter (passes of the
                                                       layer stack run for
                                                       them: ut_steps a token
                                                       for a looped decoder,
                                                       1 otherwise; over
                                                       tokens, passes a token)
        dynamo_tpu_engine_decode_kv_blocks_walked_total
                                                       counter (K/V blocks the
                                                       rows of the decode
                                                       dispatches own,
                                                       ceil(context / block)
                                                       a row: what the decode
                                                       kernel fetches a layer)
        dynamo_tpu_engine_decode_kv_blocks_group_bound_total
                                                       counter (what fetching
                                                       every slot of a group
                                                       up to the group's
                                                       longest row took for
                                                       the same dispatches;
                                                       1 - walked / bound is
                                                       the share of fetches a
                                                       row's own walk spares)
    The ``moe_*`` and ``state_*`` three are counted on the device, inside the
    model's forward, and read back with each dispatch's outputs; the others
    on the host from lengths it already has.
    """

    def __init__(self) -> None:
        self.reset()

    def record_decode(self, rows: int) -> None:
        self.decode_dispatches_total += 1
        self.decode_rows_dispatched_total += rows

    def record_finish(self) -> None:
        self.requests_finished_total += 1

    def record_cut_short(self) -> None:
        self.requests_cut_short_total += 1

    def record_first_token(self, seconds: float, turn_wait: float,
                           prefill_span: float) -> None:
        self.first_tokens_total += 1
        self.first_token_seconds_total += seconds
        self.turn_wait_seconds_total += turn_wait
        self.prefill_span_seconds_total += prefill_span

    def record_ahead(self) -> None:
        self.ahead_dispatches_total += 1

    def record_ahead_discard(self) -> None:
        self.ahead_discards_total += 1

    def record_drain(self) -> None:
        self.pipeline_drains_total += 1

    def record_operands(self, buffers: int) -> None:
        self.operand_buffers_total += buffers

    def record_prompt(self, tokens: int, cached: int) -> None:
        self.prompt_tokens_admitted_total += tokens
        self.prompt_tokens_cached_total += cached

    def record_sparse_decode(self, context: int, selected: int) -> None:
        self.attn_context_tokens_total += context
        self.attn_selected_tokens_total += selected

    def record_experts(self, picks: int, held: int, calls: int,
                       touched: int) -> None:
        self.moe_router_picks_total += picks
        self.moe_held_picks_total += held
        self.moe_expert_layer_calls_total += calls
        self.moe_experts_touched_total += touched

    def record_state(self, tokens: int, resets: int, mismatches: int) -> None:
        self.state_tokens_total += tokens
        self.state_resets_total += resets
        self.state_position_mismatches_total += mismatches

    def record_loop(self, tokens: int, passes: int) -> None:
        self.loop_tokens_total += tokens
        self.loop_passes_total += passes

    def record_decode_blocks(self, walked: int, group_bound: int) -> None:
        self.decode_kv_blocks_walked_total += walked
        self.decode_kv_blocks_group_bound_total += group_bound

    def reset(self) -> None:
        """Test isolation hook — the counters are process-global."""
        self.decode_dispatches_total = 0
        self.decode_rows_dispatched_total = 0
        self.requests_finished_total = 0
        self.requests_cut_short_total = 0
        self.first_tokens_total = 0
        self.first_token_seconds_total = 0.0
        self.turn_wait_seconds_total = 0.0
        self.prefill_span_seconds_total = 0.0
        self.ahead_dispatches_total = 0
        self.ahead_discards_total = 0
        self.pipeline_drains_total = 0
        self.operand_buffers_total = 0
        self.prompt_tokens_admitted_total = 0
        self.prompt_tokens_cached_total = 0
        self.attn_context_tokens_total = 0
        self.attn_selected_tokens_total = 0
        self.moe_router_picks_total = 0
        self.moe_held_picks_total = 0
        self.moe_expert_layer_calls_total = 0
        self.moe_experts_touched_total = 0
        self.state_tokens_total = 0
        self.state_resets_total = 0
        self.state_position_mismatches_total = 0
        self.loop_tokens_total = 0
        self.loop_passes_total = 0
        self.decode_kv_blocks_walked_total = 0
        self.decode_kv_blocks_group_bound_total = 0


request_counters = RequestCounters()


# The mesh the engine of this process runs on, written when an ``EngineCore``
# is built (the last one built wins; its own ``metrics()`` says the same):
#
#     dynamo_tpu_engine_mesh_tp       gauge (size of the tensor-parallel axis
#                                     "model"; 1 with no mesh)
#     dynamo_tpu_engine_mesh_devices  gauge (devices of the mesh; 1 with no mesh)
#
# A number of a sharded server (a step time, a collective's share) means
# something else than one chip's: a scrape says which it is looking at.
mesh_shape = {"tp": 1, "devices": 1}

# The KV cache of that engine, written beside it:
#
#     dynamo_tpu_engine_cache_layers        gauge (layers of the cache: the
#                                           model's layers, times its passes
#                                           for a looped decoder)
#     dynamo_tpu_engine_kv_bytes_per_token  gauge (bytes one token holds
#                                           across all of them: what sizes
#                                           num_blocks and a block transfer)
#     dynamo_tpu_engine_state_layers        gauge (layers that keep a
#                                           recurrent state per slot; 0 for
#                                           a model without one)
#     dynamo_tpu_engine_state_bytes_per_slot  gauge (what one slot's state
#                                           holds across them, whatever the
#                                           sequence's length)
#     dynamo_tpu_engine_prefix_reuse        gauge (1: cached blocks are
#                                           reused; 0: off, by configuration
#                                           or because of such a state)
cache_shape = {"layers": 0, "bytes_per_token": 0, "state_layers": 0,
               "state_bytes_per_slot": 0, "prefix_reuse": 1}
