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

``ENGINE_COUNTS`` is the one declaration of what an ``EngineCore`` counts:
the engine's store, ``metrics()``, the render, ``SCHEMA``'s rows and the
``EngineMetric`` constants of those names all follow it (``EngineCount``).

Zero-dependency base layer (like the rest of ``obs/``): importable
from the engine, llm, components, benchmarks and tests without cycles.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

__all__ = [
    "HTTP_PREFIX", "FAULT_PREFIX", "ENGINE_PREFIX", "KV_PREFIX",
    "STREAM_PREFIX", "SHARD_PREFIX", "PERF_PREFIX", "ROUTER_PREFIX",
    "HttpMetric", "FaultMetric", "EngineMetric", "KvTransferMetric",
    "KvStreamMetric", "KvShardMetric", "PerfMetric", "RouterMetric",
    "EngineCount", "PREFILL_FAMILY", "REQUEST_FAMILY", "ENGINE_COUNTS",
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
    """Engine plane.  The names below are the families that stay
    hand-rendered: the persist tier (``engine/counters.py``
    ``PersistCounters``) and the step timeline (``obs/timeline.py``).
    Every unlabelled count and gauge an ``EngineCore`` produces is an
    entry of ``ENGINE_COUNTS`` further down, which adds its constant
    here (``dynamo_tpu_engine_loop_passes_total`` ->
    ``EngineMetric.LOOP_PASSES_TOTAL``)."""

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
    # busy steps by what they dispatched (class: prefill, decode, mixed)
    STEP_CLASS_STEPS_TOTAL = "dynamo_tpu_engine_step_class_steps_total"
    STEP_CLASS_WALL_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_wall_seconds_total")
    STEP_CLASS_DEVICE_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_device_seconds_total")
    STEP_CLASS_LAUNCH_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_launch_seconds_total")
    STEP_CLASS_UPLOAD_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_upload_seconds_total")
    STEP_CLASS_READBACK_SECONDS_TOTAL = (
        "dynamo_tpu_engine_step_class_readback_seconds_total")
    # readbacks that found their dispatch finished: the host had no slack
    STEP_CLASS_READY_READBACKS_TOTAL = (
        "dynamo_tpu_engine_step_class_ready_readbacks_total")
    # did the device wait?  Launches, those before which it had run dry,
    # and for how long at least (lo) and at most (hi) (obs/timeline.py)
    LAUNCHES_TOTAL = "dynamo_tpu_engine_launches_total"
    STARVED_LAUNCHES_TOTAL = "dynamo_tpu_engine_starved_launches_total"
    DEVICE_WAIT_SECONDS_TOTAL = "dynamo_tpu_engine_device_wait_seconds_total"


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


class EngineCount(NamedTuple):
    """One unlabelled count or gauge of an ``EngineCore``, declared once.

    ``engine/counters.py`` builds the engine's store from these entries (one
    attribute each, named ``attr``), ``EngineCore.metrics()`` is ``key ->
    value`` over them, ``/metrics`` renders ``name`` in the table's order,
    ``SCHEMA`` and ``EngineMetric`` take ``name`` from here, and the metrics
    manifest and the two listings of ``docs/observability.md`` are generated
    from them (``dynamo-tpu lint --metrics --update-baseline``)."""

    name: Optional[str]   # on /metrics, spelled in full; None: metrics() only
    kind: str             # "counter" | "gauge"
    help: str
    key: Optional[str]    # in EngineCore.metrics(); None: /metrics only
    # a derived value: one attribute of the store over another, 0.0 while
    # the second is 0 (its operands need no entry of their own)
    ratio: Optional[tuple[str, str]] = None
    initial: float = 0    # what a store starts from, and /metrics with no engine

    @property
    def attr(self) -> str:
        """The store's attribute: the key, else the name less the prefix."""
        return self.key or _short(self.name)

    def value(self, counts):
        """This entry's value in ``counts`` (a store, or a sum of stores)."""
        if self.ratio is None:
            return getattr(counts, self.attr)
        over = getattr(counts, self.ratio[1])
        return getattr(counts, self.ratio[0]) / over if over else 0.0


def _short(name: str) -> str:
    return name[len(ENGINE_PREFIX) + 1:]


_SHORT = object()


def _count(name, kind, help, *, key=_SHORT, ratio=None, initial=0):
    """An entry whose ``metrics()`` key is the name less the prefix unless
    ``key`` says otherwise (None: none)."""
    return EngineCount(name, kind, help,
                       _short(name) if key is _SHORT else key, ratio, initial)


# In the order /metrics renders them.  The first family goes out before the
# persist, stream, shard and timeline families, the second after them.
PREFILL_FAMILY = (
    _count("dynamo_tpu_engine_prefill_dispatches_total", "counter",
           "prefill dispatches, any path"),
    _count("dynamo_tpu_engine_prefill_tokens_total", "counter",
           "prompt tokens they computed", key=None),
    _count("dynamo_tpu_engine_prefill_batch_occupancy", "gauge",
           "mean sequences a prefill dispatch",
           ratio=("prefill_rows_dispatched", "prefill_dispatches_total")),
    _count("dynamo_tpu_engine_prefill_budget_utilization", "gauge",
           "tokens packed over the token budget offered, over the batched "
           "dispatches (a one-request or seq-parallel dispatch offers none)",
           ratio=("prefill_budget_used", "prefill_budget_offered")),
    _count("dynamo_tpu_engine_prefill_ready_rows_total", "counter",
           "requests ready to prefill, summed at every prefill dispatch: "
           "over the dispatches, the backlog a served request stood in"),
    _count("dynamo_tpu_engine_unified_dispatches_total", "counter",
           "mixed prefill+decode dispatches of the unified token-budget "
           "scheduler: turns that made one dispatch of two"),
    _count("dynamo_tpu_engine_unified_decode_rows_total", "counter",
           "decode rows packed over them", key="unified_decode_rows"),
    _count("dynamo_tpu_engine_unified_prefill_tokens_total", "counter",
           "prefill tokens packed over them", key="unified_prefill_tokens"),
    _count("dynamo_tpu_engine_unified_budget_utilization", "gauge",
           "decode rows + prefill tokens over the flat-axis budget offered",
           ratio=("unified_budget_used", "unified_budget_offered")),
    _count("dynamo_tpu_engine_prefill_programs_total", "counter",
           "distinct programs the prefill entry points hold (the jit caches "
           "of the one-request, the batched and the unified dispatch): one a "
           "token bucket where the flash kernel streams the prefix, one a "
           "bucket x cached-prefix bucket where the prefix sizes a gather"),
)

REQUEST_FAMILY = (
    _count("dynamo_tpu_engine_decode_dispatches_total", "counter",
           "pure-decode dispatches: burst or speculative verify"),
    _count("dynamo_tpu_engine_decode_rows_dispatched_total", "counter",
           "running rows packed over them"),
    _count("dynamo_tpu_engine_requests_finished_total", "counter",
           "requests finished, for any reason"),
    _count("dynamo_tpu_engine_requests_cut_short_total", "counter",
           "of those, ended with `length` because KV block space ran out "
           "(not max_tokens, not max_model_len)"),
    _count("dynamo_tpu_engine_first_tokens_total", "counter",
           "requests that emitted a token"),
    _count("dynamo_tpu_engine_first_token_seconds_total", "counter",
           "sum of first emit - submit: TTFT as the engine sees it",
           initial=0.0),
    _count("dynamo_tpu_engine_turn_wait_seconds_total", "counter",
           "of that, the sum of first dispatch that carried the request - "
           "slot: in a slot, nothing issued for it yet", initial=0.0),
    _count("dynamo_tpu_engine_prefill_span_seconds_total", "counter",
           "and of first emit - that dispatch (its chunks, the turns between "
           "them, the readback); with the queue wait the three add up to the "
           "TTFT", initial=0.0),
    _count("dynamo_tpu_engine_ahead_dispatches_total", "counter",
           "decode dispatches issued with a dispatch in flight: over decode "
           "dispatches, how often the host's round trip is hidden"),
    _count("dynamo_tpu_engine_ahead_discards_total", "counter",
           "rows whose ahead-sample a stop found one dispatch late threw "
           "away: the mechanism's waste"),
    _count("dynamo_tpu_engine_pipeline_drains_total", "counter",
           "turns that read back before they could issue (or had nothing to "
           "issue): why the ahead share is not 1"),
    _count("dynamo_tpu_engine_operand_buffers_total", "counter",
           "host->device buffers the dispatches' operands took, buffers put "
           "x devices put to: over prefill + decode dispatches, 1 x the "
           "devices (one packed buffer a dispatch)"),
    _count("dynamo_tpu_engine_outputs_emitted_total", "counter",
           "outputs the engine thread handed over to the streams' event "
           "loops (AsyncLLMEngine; a core driven with a plain emit callable "
           "counts none)"),
    _count("dynamo_tpu_engine_emit_hops_total", "counter",
           "wake-ups posted to an event loop for them, one a loop a flush "
           "(the end of a dispatch's host work, of a step): outputs over "
           "hops is cellbench's http.outputs_per_hop, rows a dispatch where "
           "the hand-over is batched, 1 where every output woke the loop"),
    _count("dynamo_tpu_engine_prompt_tokens_admitted_total", "counter",
           "prompt tokens of requests whose prefill completed"),
    _count("dynamo_tpu_engine_prompt_tokens_cached_total", "counter",
           "of those, the tokens served from reused blocks: over admitted, "
           "the prefix cache's hit share (cellbench's kv.prefix_hit_pct)"),
    _count("dynamo_tpu_engine_prompt_blocks_admitted_total", "counter",
           "full blocks of the prompts whose block chain admission built"),
    _count("dynamo_tpu_engine_prompt_blocks_reused_total", "counter",
           "of those, the blocks taken from the chain memo (tokens.py "
           "BlockChainMemo) and not hashed again: over admitted, cellbench's "
           "kv.chain_reuse_pct"),
    _count("dynamo_tpu_engine_attn_context_tokens_total", "counter",
           "latent-attention models: cached positions the decode rows "
           "dispatched could see, summed"),
    _count("dynamo_tpu_engine_attn_selected_tokens_total", "counter",
           "of those, the positions attended to, min(context, index_topk) a "
           "row, all of them without an indexer: over context, how sparse "
           "decode attention was"),
    _count("dynamo_tpu_engine_attn_fetched_tokens_total", "counter",
           "latent attention without an indexer: cached rows the decode "
           "kernel fetches a layer for those rows, what a group of rows that "
           "ask one document shares counted once (over context, cellbench's "
           "attn.fetched_pct)"),
    _count("dynamo_tpu_engine_index_keys_table_total", "counter",
           "latent attention with an indexer: rows x positions of the block "
           "tables the decode dispatches carried, summed (what a gather of "
           "every row's whole table copies a full layer)"),
    _count("dynamo_tpu_engine_index_keys_read_total", "counter",
           "of those, the index keys the decode indexer fetches a full layer "
           "where it scores them in place (the model's index_keys_read, the "
           "kernel's own arithmetic: whole blocks up to each row's length, "
           "what a group of rows that ask one document shares counted once): "
           "over the table, cellbench's attn.index_keys_read_pct"),
    _count("dynamo_tpu_engine_prefill_masked_tokens_total", "counter",
           "latent attention with an indexer: prompt tokens computed whose "
           "chunk attended in masked form, every key of the context scored "
           "once a tile of queries (the model's attends_masked, the rule its "
           "forward traced by; the others gathered their rows): over the "
           "prompt tokens computed, cellbench's attn.prefill_masked_pct"),
    _count("dynamo_tpu_engine_moe_router_picks_total", "counter",
           "experts the router picked for the real tokens of every dispatch "
           "(top-k a token and expert layer), counted on the device in the "
           "cache's moe_counts and read back with each dispatch"),
    _count("dynamo_tpu_engine_moe_held_picks_total", "counter",
           "of those, the picks on the experts this chip holds: the rows its "
           "experts computed (over router picks, moe.held_pick_pct)"),
    _count("dynamo_tpu_engine_moe_expert_layer_calls_total", "counter",
           "expert layers run, one a layer and dispatch"),
    _count("dynamo_tpu_engine_moe_experts_touched_total", "counter",
           "held experts with at least one row, summed over layers: x an "
           "expert's bytes, what the grouped matmul streamed"),
    _count("dynamo_tpu_engine_moe_skip_picks_total", "counter",
           "of the router's picks, those on a skip output (models/zaya.py: a "
           "token that takes no expert in that layer); router picks = held "
           "picks + skip picks there (over router picks, moe.skip_pick_pct)"),
    _count("dynamo_tpu_engine_state_tokens_total", "counter",
           "a model with recurrent layers (docs/linear_state.md): real "
           "tokens x such layers advanced, counted on the device like the "
           "moe_* four"),
    _count("dynamo_tpu_engine_state_resets_total", "counter",
           "sequences started from a zero state (position 0): one a request, "
           "since such a model reuses no prefix"),
    _count("dynamo_tpu_engine_state_position_mismatches_total", "counter",
           "rows that went on at another position than their slot's state "
           "stood at: 0, the slot contract"),
    _count("dynamo_tpu_engine_mesh_tp", "gauge",
           "size of the tensor-parallel axis `model` of the engine's mesh; 1 "
           "with no mesh", initial=1),
    _count("dynamo_tpu_engine_mesh_devices", "gauge",
           "devices of that mesh; 1 with no mesh", initial=1),
    _count("dynamo_tpu_engine_loop_tokens_total", "counter",
           "tokens that went out in a prefill or decode dispatch"),
    _count("dynamo_tpu_engine_loop_passes_total", "counter",
           "passes of the layer stack run for them: ut_steps a token for a "
           "looped decoder (docs/looped_layers.md), 1 otherwise; over "
           "tokens, loop.passes_per_token"),
    _count("dynamo_tpu_engine_decode_kv_blocks_walked_total", "counter",
           "K/V blocks the rows of the decode dispatches own, ceil(context / "
           "block) a row: what the decode kernel fetches a layer"),
    _count("dynamo_tpu_engine_decode_kv_blocks_group_bound_total", "counter",
           "what fetching every slot of a kernel group up to the group's "
           "longest row took for the same dispatches; 1 - walked / bound is "
           "the share of fetches a row's own walk spares"),
    _count("dynamo_tpu_engine_decode_kv_window_blocks_walked_total",
           "counter",
           "K/V blocks the decode rows' walks take in the layers of "
           "sliding-window attention, summed over those layers: a row's "
           "blocks from the one that holds position context - window to its "
           "last (docs/window_layers.md); 0 for a model without such layers"),
    _count("dynamo_tpu_engine_decode_kv_window_blocks_span_total", "counter",
           "the blocks those rows own, ceil(context / block) a row, summed "
           "over the same layers: what the walk would take without the "
           "window; walked / span is attn.window_walked_pct"),
    _count("dynamo_tpu_engine_cache_layers", "gauge",
           "layers of the K/V cache: the model's layers, times its passes "
           "for a looped decoder"),
    _count("dynamo_tpu_engine_kv_bytes_per_token", "gauge",
           "bytes one token holds across all of them: what sizes num_blocks "
           "and a block transfer"),
    _count("dynamo_tpu_engine_state_layers", "gauge",
           "layers that keep a recurrent state per slot; 0 for a model "
           "without one"),
    _count("dynamo_tpu_engine_state_bytes_per_slot", "gauge",
           "what one slot's state holds across them, whatever the "
           "sequence's length"),
    _count("dynamo_tpu_engine_prefix_reuse", "gauge",
           "1: cached blocks are reused; 0: off, by configuration or because "
           "a recurrent state rules it out", initial=1),
    # on EngineCore.metrics() alone
    _count(None, "counter", "tokens emitted", key="tokens_generated"),
    _count(None, "counter", "speculative verify dispatches",
           key="spec_steps"),
    _count(None, "counter", "tokens the n-gram lookup or the draft proposed",
           key="spec_proposed"),
    _count(None, "counter", "proposals the model agreed with",
           key="spec_accepted"),
    _count(None, "counter", "jax.device_get calls of the step loop",
           key="device_gets_total"),
    _count(None, "gauge", "1: the decode program updates a slot's recurrent "
           "state in one kernel", key="state_update_kernel"),
    _count(None, "gauge", "layers whose attention is masked by the model's "
           "sliding window (every layer of a uniform window model, the "
           "sliding_attention layers of layer_types); 0 without a window",
           key="window_layers"),
    _count(None, "gauge", "that window, in tokens; 0 without one",
           key="sliding_window"),
)

ENGINE_COUNTS = PREFILL_FAMILY + REQUEST_FAMILY

for _e in ENGINE_COUNTS:
    if _e.name:
        setattr(EngineMetric, _short(_e.name).upper(), _e.name)


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
    EngineMetric.STEP_CLASS_STEPS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_WALL_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_DEVICE_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_LAUNCH_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_UPLOAD_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_READBACK_SECONDS_TOTAL: ("counter", ("class",)),
    EngineMetric.STEP_CLASS_READY_READBACKS_TOTAL: ("counter", ("class",)),
    EngineMetric.LAUNCHES_TOTAL: ("counter", ()),
    EngineMetric.STARVED_LAUNCHES_TOTAL: ("counter", ()),
    EngineMetric.DEVICE_WAIT_SECONDS_TOTAL: ("counter", ("bound",)),
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
    **{e.name: (e.kind, ()) for e in ENGINE_COUNTS if e.name},
}


def metric_names() -> list[str]:
    """Every registered metric name, sorted (registry coverage tests)."""
    return sorted(SCHEMA)
