"""Canonical registry of every metric name on the ``/metrics`` surface.

The same ``protocol.py`` CoordOp idiom that pinned the coordinator op
strings: plain class-level ``NAME = "literal"`` constants, one class
per metric family, every name spelled in FULL (no prefix composition)
so the metrics lint plane (``analysis/metcheck.py``, dtmet) can bottom
every render/scrape site out at its literal through the dtwire-style
const table.  Render sites (``llm/http/metrics.py``,
``components/metrics.py``), scrape sites (``benchmarks/scrape.py``)
and tests all import these names — renaming a metric is one edit here,
and a missed consumer becomes an ImportError or an MT002 finding,
never a silently-zero bench column.

``SCHEMA`` is the committed name -> (type, label set) contract the
dtmet census is checked against; ``docs/observability.md``'s metric
reference table is generated from it (drift fails ``lint --metrics``).

Zero-dependency base layer (like the rest of ``obs/``): importable
from the engine, llm, components, benchmarks and tests without cycles.
"""

from __future__ import annotations

__all__ = [
    "HTTP_PREFIX", "FAULT_PREFIX", "ENGINE_PREFIX", "KV_PREFIX",
    "STREAM_PREFIX", "SHARD_PREFIX", "PERF_PREFIX", "ROUTER_PREFIX",
    "HttpMetric", "FaultMetric", "EngineMetric", "KvTransferMetric",
    "KvStreamMetric", "KvShardMetric", "PerfMetric", "RouterMetric",
    "SCHEMA", "metric_names",
]

# family prefixes — kept ONLY for prefix-scoped scraping/grouping
# (benchmarks/scrape.py family reads); metric names below never
# compose them at runtime
HTTP_PREFIX = "dynamo_tpu_http_service"
FAULT_PREFIX = "dynamo_tpu_fault"
ENGINE_PREFIX = "dynamo_tpu_engine"
KV_PREFIX = "dynamo_tpu_kv_transfer"
STREAM_PREFIX = "dynamo_tpu_kv_stream"
SHARD_PREFIX = "dynamo_tpu_kv_shard"
PERF_PREFIX = "dynamo_tpu_perf"
ROUTER_PREFIX = "dynamo_tpu"


class HttpMetric:
    """HTTP service plane (``llm/http/metrics.py`` Metrics.render)."""

    REQUESTS_TOTAL = "dynamo_tpu_http_service_requests_total"
    INFLIGHT_REQUESTS = "dynamo_tpu_http_service_inflight_requests"
    OUTPUT_TOKENS_TOTAL = "dynamo_tpu_http_service_output_tokens_total"
    ADMISSION_SHED_TOTAL = "dynamo_tpu_http_service_admission_shed_total"
    TTFT_SECONDS = "dynamo_tpu_http_service_ttft_seconds"
    INTER_TOKEN_SECONDS = "dynamo_tpu_http_service_inter_token_seconds"
    QUEUE_WAIT_SECONDS = "dynamo_tpu_http_service_queue_wait_seconds"
    # the front end's two ends: handler entry -> engine submit, and an
    # output's emit on the engine thread -> its chunk written to the socket
    PRE_SUBMIT_SECONDS = "dynamo_tpu_http_service_pre_submit_seconds"
    EMIT_LAG_SECONDS = "dynamo_tpu_http_service_emit_lag_seconds"
    REQUEST_SECONDS = "dynamo_tpu_http_service_request_seconds"


class FaultMetric:
    """Fault plane (``fault/counters.py`` process-global counters)."""

    MIGRATIONS_TOTAL = "dynamo_tpu_fault_migrations_total"
    DRAINS_IN_PROGRESS = "dynamo_tpu_fault_drains_in_progress"
    SUSPECT_INSTANCES = "dynamo_tpu_fault_suspect_instances"


class EngineMetric:
    """Engine plane: prefill batching, unified dispatch, persist tier
    (``engine/counters.py``) and the step timeline
    (``obs/timeline.py``)."""

    PREFILL_DISPATCHES_TOTAL = "dynamo_tpu_engine_prefill_dispatches_total"
    PREFILL_TOKENS_TOTAL = "dynamo_tpu_engine_prefill_tokens_total"
    PREFILL_BATCH_OCCUPANCY = "dynamo_tpu_engine_prefill_batch_occupancy"
    PREFILL_BUDGET_UTILIZATION = (
        "dynamo_tpu_engine_prefill_budget_utilization")
    PREFILL_READY_ROWS_TOTAL = "dynamo_tpu_engine_prefill_ready_rows_total"
    UNIFIED_DISPATCHES_TOTAL = "dynamo_tpu_engine_unified_dispatches_total"
    UNIFIED_DECODE_ROWS_TOTAL = "dynamo_tpu_engine_unified_decode_rows_total"
    UNIFIED_PREFILL_TOKENS_TOTAL = (
        "dynamo_tpu_engine_unified_prefill_tokens_total")
    UNIFIED_BUDGET_UTILIZATION = (
        "dynamo_tpu_engine_unified_budget_utilization")
    PERSIST_HITS_TOTAL = "dynamo_tpu_engine_persist_hits_total"
    PERSIST_MISSES_TOTAL = "dynamo_tpu_engine_persist_misses_total"
    PERSIST_RESTORED_TOKENS_TOTAL = (
        "dynamo_tpu_engine_persist_restored_tokens_total")
    PERSIST_SPILL_BYTES_TOTAL = "dynamo_tpu_engine_persist_spill_bytes_total"
    PERSIST_RESIDENT_BYTES = "dynamo_tpu_engine_persist_resident_bytes"
    STEPS_TOTAL = "dynamo_tpu_engine_steps_total"
    BUSY_STEPS_TOTAL = "dynamo_tpu_engine_busy_steps_total"
    STEP_WALL_SECONDS_TOTAL = "dynamo_tpu_engine_step_wall_seconds_total"
    STEP_PHASE_SECONDS_TOTAL = "dynamo_tpu_engine_step_phase_seconds_total"
    HOST_GAP_MS_PER_TURN = "dynamo_tpu_engine_host_gap_ms_per_turn"
    STEP_WALL_MS_EWMA = "dynamo_tpu_engine_step_wall_ms_ewma"
    HOST_GAP_MS_EWMA = "dynamo_tpu_engine_host_gap_ms_ewma"
    # busy steps by what they dispatched (class: prefill, decode, mixed)
    STEP_CLASS_STEPS_TOTAL = "dynamo_tpu_engine_step_class_steps_total"
    STEP_CLASS_WALL_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_wall_seconds_total")
    STEP_CLASS_DEVICE_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_device_seconds_total")
    STEP_CLASS_LAUNCH_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_launch_seconds_total")
    STEP_CLASS_READBACK_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_readback_seconds_total")
    # engine/counters.py RequestCounters
    DECODE_DISPATCHES_TOTAL = "dynamo_tpu_engine_decode_dispatches_total"
    DECODE_ROWS_DISPATCHED_TOTAL = (
        "dynamo_tpu_engine_decode_rows_dispatched_total")
    REQUESTS_FINISHED_TOTAL = "dynamo_tpu_engine_requests_finished_total"
    REQUESTS_CUT_SHORT_TOTAL = "dynamo_tpu_engine_requests_cut_short_total"
    FIRST_TOKENS_TOTAL = "dynamo_tpu_engine_first_tokens_total"
    FIRST_TOKEN_SECONDS_TOTAL = (
        "dynamo_tpu_engine_first_token_seconds_total")
    # a first token's stages behind the slot (EngineCore._first_token)
    TURN_WAIT_SECONDS_TOTAL = "dynamo_tpu_engine_turn_wait_seconds_total"
    PREFILL_SPAN_SECONDS_TOTAL = (
        "dynamo_tpu_engine_prefill_span_seconds_total")
    # dispatch-ahead (EngineCore._settle)
    AHEAD_DISPATCHES_TOTAL = "dynamo_tpu_engine_ahead_dispatches_total"
    AHEAD_DISCARDS_TOTAL = "dynamo_tpu_engine_ahead_discards_total"
    PIPELINE_DRAINS_TOTAL = "dynamo_tpu_engine_pipeline_drains_total"
    # operand upload (EngineCore._upload_dispatch)
    OPERAND_BUFFERS_TOTAL = "dynamo_tpu_engine_operand_buffers_total"
    # prefix reuse and sparse attention, from lengths the host has
    PROMPT_TOKENS_ADMITTED_TOTAL = (
        "dynamo_tpu_engine_prompt_tokens_admitted_total")
    PROMPT_TOKENS_CACHED_TOTAL = "dynamo_tpu_engine_prompt_tokens_cached_total"
    ATTN_CONTEXT_TOKENS_TOTAL = "dynamo_tpu_engine_attn_context_tokens_total"
    ATTN_SELECTED_TOKENS_TOTAL = "dynamo_tpu_engine_attn_selected_tokens_total"
    # what the expert layers counted on the device (a share of the experts)
    MOE_ROUTER_PICKS_TOTAL = "dynamo_tpu_engine_moe_router_picks_total"
    MOE_HELD_PICKS_TOTAL = "dynamo_tpu_engine_moe_held_picks_total"
    MOE_EXPERT_LAYER_CALLS_TOTAL = (
        "dynamo_tpu_engine_moe_expert_layer_calls_total")
    MOE_EXPERTS_TOUCHED_TOTAL = "dynamo_tpu_engine_moe_experts_touched_total"
    # what the recurrent layers did, counted on the device
    STATE_TOKENS_TOTAL = "dynamo_tpu_engine_state_tokens_total"
    STATE_RESETS_TOTAL = "dynamo_tpu_engine_state_resets_total"
    STATE_POSITION_MISMATCHES_TOTAL = (
        "dynamo_tpu_engine_state_position_mismatches_total")
    # tokens dispatched and the passes of the layer stack run for them
    LOOP_TOKENS_TOTAL = "dynamo_tpu_engine_loop_tokens_total"
    LOOP_PASSES_TOTAL = "dynamo_tpu_engine_loop_passes_total"
    # K/V blocks the decode rows own, and what a group-max fetch took
    DECODE_KV_BLOCKS_WALKED_TOTAL = (
        "dynamo_tpu_engine_decode_kv_blocks_walked_total")
    DECODE_KV_BLOCKS_GROUP_BOUND_TOTAL = (
        "dynamo_tpu_engine_decode_kv_blocks_group_bound_total")
    # engine/counters.py mesh_shape
    MESH_TP = "dynamo_tpu_engine_mesh_tp"
    MESH_DEVICES = "dynamo_tpu_engine_mesh_devices"
    # engine/counters.py cache_shape
    CACHE_LAYERS = "dynamo_tpu_engine_cache_layers"
    KV_BYTES_PER_TOKEN = "dynamo_tpu_engine_kv_bytes_per_token"
    STATE_LAYERS = "dynamo_tpu_engine_state_layers"
    STATE_BYTES_PER_SLOT = "dynamo_tpu_engine_state_bytes_per_slot"
    PREFIX_REUSE = "dynamo_tpu_engine_prefix_reuse"


class KvTransferMetric:
    """Measured KV-transfer cost edges (``obs/costs.py``)."""

    CALLS_TOTAL = "dynamo_tpu_kv_transfer_calls_total"
    BYTES_TOTAL = "dynamo_tpu_kv_transfer_bytes_total"
    SECONDS_TOTAL = "dynamo_tpu_kv_transfer_seconds_total"
    MBPS = "dynamo_tpu_kv_transfer_mbps"
    LATENCY_MS = "dynamo_tpu_kv_transfer_latency_ms"


class KvStreamMetric:
    """Streamed KV handoff (``llm/kv/stream.py`` counters)."""

    SESSIONS_TOTAL = "dynamo_tpu_kv_stream_sessions_total"
    LAYERS_SENT_TOTAL = "dynamo_tpu_kv_stream_layers_sent_total"
    BYTES_TOTAL = "dynamo_tpu_kv_stream_bytes_total"
    FALLBACKS_TOTAL = "dynamo_tpu_kv_stream_fallbacks_total"
    OVERLAP_RATIO = "dynamo_tpu_kv_stream_overlap_ratio"


class KvShardMetric:
    """Sharded control plane (``llm/kv_router/shards/`` counters)."""

    SCATTERS_TOTAL = "dynamo_tpu_kv_shard_scatters_total"
    GATHER_PARTIAL_TOTAL = "dynamo_tpu_kv_shard_gather_partial_total"
    GENERATION = "dynamo_tpu_kv_shard_generation"
    FANOUT_LATENCY_MS = "dynamo_tpu_kv_shard_fanout_latency_ms"
    LAST_FAN_OUT = "dynamo_tpu_kv_shard_last_fan_out"
    INDEX_BLOCKS = "dynamo_tpu_kv_shard_index_blocks"
    RESIDENT_KEYS = "dynamo_tpu_kv_shard_resident_keys"


class PerfMetric:
    """dtperf plane: static roofline predictions + runtime
    predicted-vs-measured reconciliation (``obs/perfmodel.py``)."""

    PREDICTED_STEP_MS = "dynamo_tpu_perf_predicted_step_ms"
    PREDICTED_DISPATCH_MS = "dynamo_tpu_perf_predicted_dispatch_ms"
    MEASURED_DISPATCH_MS = "dynamo_tpu_perf_measured_dispatch_ms"
    DISPATCHES_TOTAL = "dynamo_tpu_perf_dispatches_total"
    MODEL_ERROR_RATIO = "dynamo_tpu_perf_model_error_ratio"


class RouterMetric:
    """Standalone metrics aggregation component
    (``components/metrics.py`` PrometheusMetricsCollector)."""

    KV_BLOCKS_ACTIVE = "dynamo_tpu_kv_blocks_active"
    KV_BLOCKS_TOTAL = "dynamo_tpu_kv_blocks_total"
    REQUEST_ACTIVE_SLOTS = "dynamo_tpu_request_active_slots"
    REQUESTS_WAITING = "dynamo_tpu_requests_waiting"
    KV_CACHE_USAGE = "dynamo_tpu_kv_cache_usage"
    ROUTING_DECISIONS_TOTAL = "dynamo_tpu_routing_decisions_total"
    KV_HIT_RATE_PERCENT = "dynamo_tpu_kv_hit_rate_percent"


# name -> (type, labels) — the committed label-schema contract.
# Histogram entries list their sample labels WITHOUT the implicit "le"
# (the render side adds it on _bucket lines); the dtmet census
# normalizes the same way before comparing.
SCHEMA: dict[str, tuple[str, tuple[str, ...]]] = {
    HttpMetric.REQUESTS_TOTAL: ("counter", ("model", "endpoint", "status")),
    HttpMetric.INFLIGHT_REQUESTS: ("gauge", ("model",)),
    HttpMetric.OUTPUT_TOKENS_TOTAL: ("counter", ("model",)),
    HttpMetric.ADMISSION_SHED_TOTAL: ("counter", ("model", "priority")),
    HttpMetric.TTFT_SECONDS: ("histogram", ("model",)),
    HttpMetric.INTER_TOKEN_SECONDS: ("histogram", ("model",)),
    HttpMetric.QUEUE_WAIT_SECONDS: ("histogram", ("model",)),
    HttpMetric.PRE_SUBMIT_SECONDS: ("histogram", ("model",)),
    HttpMetric.EMIT_LAG_SECONDS: ("histogram", ("model",)),
    HttpMetric.REQUEST_SECONDS: ("histogram", ("model", "status")),
    FaultMetric.MIGRATIONS_TOTAL: ("counter", ()),
    FaultMetric.DRAINS_IN_PROGRESS: ("gauge", ()),
    FaultMetric.SUSPECT_INSTANCES: ("gauge", ()),
    EngineMetric.PREFILL_DISPATCHES_TOTAL: ("counter", ()),
    EngineMetric.PREFILL_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.PREFILL_BATCH_OCCUPANCY: ("gauge", ()),
    EngineMetric.PREFILL_BUDGET_UTILIZATION: ("gauge", ()),
    EngineMetric.PREFILL_READY_ROWS_TOTAL: ("counter", ()),
    EngineMetric.UNIFIED_DISPATCHES_TOTAL: ("counter", ()),
    EngineMetric.UNIFIED_DECODE_ROWS_TOTAL: ("counter", ()),
    EngineMetric.UNIFIED_PREFILL_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.UNIFIED_BUDGET_UTILIZATION: ("gauge", ()),
    EngineMetric.PERSIST_HITS_TOTAL: ("counter", ()),
    EngineMetric.PERSIST_MISSES_TOTAL: ("counter", ()),
    EngineMetric.PERSIST_RESTORED_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.PERSIST_SPILL_BYTES_TOTAL: ("counter", ()),
    EngineMetric.PERSIST_RESIDENT_BYTES: ("gauge", ()),
    EngineMetric.STEPS_TOTAL: ("counter", ()),
    EngineMetric.BUSY_STEPS_TOTAL: ("counter", ()),
    EngineMetric.STEP_WALL_SECONDS_TOTAL: ("counter", ()),
    EngineMetric.STEP_PHASE_SECONDS_TOTAL: ("counter", ("phase",)),
    EngineMetric.HOST_GAP_MS_PER_TURN: ("gauge", ()),
    EngineMetric.STEP_WALL_MS_EWMA: ("gauge", ()),
    EngineMetric.HOST_GAP_MS_EWMA: ("gauge", ()),
    EngineMetric.STEP_CLASS_STEPS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_WALL_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_DEVICE_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_LAUNCH_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_READBACK_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.DECODE_DISPATCHES_TOTAL: ("counter", ()),
    EngineMetric.DECODE_ROWS_DISPATCHED_TOTAL: ("counter", ()),
    EngineMetric.REQUESTS_FINISHED_TOTAL: ("counter", ()),
    EngineMetric.REQUESTS_CUT_SHORT_TOTAL: ("counter", ()),
    EngineMetric.FIRST_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.FIRST_TOKEN_SECONDS_TOTAL: ("counter", ()),
    EngineMetric.TURN_WAIT_SECONDS_TOTAL: ("counter", ()),
    EngineMetric.PREFILL_SPAN_SECONDS_TOTAL: ("counter", ()),
    EngineMetric.AHEAD_DISPATCHES_TOTAL: ("counter", ()),
    EngineMetric.AHEAD_DISCARDS_TOTAL: ("counter", ()),
    EngineMetric.PIPELINE_DRAINS_TOTAL: ("counter", ()),
    EngineMetric.OPERAND_BUFFERS_TOTAL: ("counter", ()),
    EngineMetric.PROMPT_TOKENS_ADMITTED_TOTAL: ("counter", ()),
    EngineMetric.PROMPT_TOKENS_CACHED_TOTAL: ("counter", ()),
    EngineMetric.ATTN_CONTEXT_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.ATTN_SELECTED_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.MOE_ROUTER_PICKS_TOTAL: ("counter", ()),
    EngineMetric.MOE_HELD_PICKS_TOTAL: ("counter", ()),
    EngineMetric.MOE_EXPERT_LAYER_CALLS_TOTAL: ("counter", ()),
    EngineMetric.MOE_EXPERTS_TOUCHED_TOTAL: ("counter", ()),
    EngineMetric.STATE_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.STATE_RESETS_TOTAL: ("counter", ()),
    EngineMetric.STATE_POSITION_MISMATCHES_TOTAL: ("counter", ()),
    EngineMetric.MESH_TP: ("gauge", ()),
    EngineMetric.MESH_DEVICES: ("gauge", ()),
    EngineMetric.LOOP_TOKENS_TOTAL: ("counter", ()),
    EngineMetric.LOOP_PASSES_TOTAL: ("counter", ()),
    EngineMetric.DECODE_KV_BLOCKS_WALKED_TOTAL: ("counter", ()),
    EngineMetric.DECODE_KV_BLOCKS_GROUP_BOUND_TOTAL: ("counter", ()),
    EngineMetric.CACHE_LAYERS: ("gauge", ()),
    EngineMetric.KV_BYTES_PER_TOKEN: ("gauge", ()),
    EngineMetric.STATE_LAYERS: ("gauge", ()),
    EngineMetric.STATE_BYTES_PER_SLOT: ("gauge", ()),
    EngineMetric.PREFIX_REUSE: ("gauge", ()),
    KvTransferMetric.CALLS_TOTAL: ("counter", ("src", "dst", "path")),
    KvTransferMetric.BYTES_TOTAL: ("counter", ("src", "dst", "path")),
    KvTransferMetric.SECONDS_TOTAL: ("counter", ("src", "dst", "path")),
    KvTransferMetric.MBPS: ("gauge", ("src", "dst", "path")),
    KvTransferMetric.LATENCY_MS: ("gauge", ("src", "dst", "path")),
    KvStreamMetric.SESSIONS_TOTAL: ("counter", ()),
    KvStreamMetric.LAYERS_SENT_TOTAL: ("counter", ()),
    KvStreamMetric.BYTES_TOTAL: ("counter", ()),
    KvStreamMetric.FALLBACKS_TOTAL: ("counter", ()),
    KvStreamMetric.OVERLAP_RATIO: ("gauge", ()),
    KvShardMetric.SCATTERS_TOTAL: ("counter", ()),
    KvShardMetric.GATHER_PARTIAL_TOTAL: ("counter", ()),
    KvShardMetric.GENERATION: ("gauge", ()),
    KvShardMetric.FANOUT_LATENCY_MS: ("histogram", ()),
    KvShardMetric.LAST_FAN_OUT: ("gauge", ()),
    KvShardMetric.INDEX_BLOCKS: ("gauge", ("shard",)),
    KvShardMetric.RESIDENT_KEYS: ("gauge", ("shard",)),
    PerfMetric.PREDICTED_STEP_MS: (
        "gauge", ("entrypoint", "config", "signature", "bound")),
    PerfMetric.PREDICTED_DISPATCH_MS: ("gauge", ("kind",)),
    PerfMetric.MEASURED_DISPATCH_MS: ("gauge", ("kind",)),
    PerfMetric.DISPATCHES_TOTAL: ("counter", ("kind",)),
    PerfMetric.MODEL_ERROR_RATIO: ("gauge", ("kind",)),
    RouterMetric.KV_BLOCKS_ACTIVE: ("gauge", ("worker",)),
    RouterMetric.KV_BLOCKS_TOTAL: ("gauge", ("worker",)),
    RouterMetric.REQUEST_ACTIVE_SLOTS: ("gauge", ("worker",)),
    RouterMetric.REQUESTS_WAITING: ("gauge", ("worker",)),
    RouterMetric.KV_CACHE_USAGE: ("gauge", ("worker",)),
    RouterMetric.ROUTING_DECISIONS_TOTAL: ("counter", ("worker",)),
    RouterMetric.KV_HIT_RATE_PERCENT: ("gauge", ("worker",)),
}


def metric_names() -> list[str]:
    """Every registered metric name, sorted (registry coverage tests)."""
    return sorted(SCHEMA)
