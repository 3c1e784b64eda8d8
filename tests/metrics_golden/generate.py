"""Regenerate the committed golden /metrics render fixtures.

    python tests/metrics_golden/generate.py

Two byte-level recordings of the repo's Prometheus text exposition —
the HTTP service surface (llm/http/metrics.py, with every
process-global counter family populated) and the standalone metrics
component (components/metrics.py) — produced from a fixed,
deterministic seeding of every producer.  tests/test_metrics_golden.py
re-renders the same seeding with CURRENT code and compares
byte-for-byte, then re-scrapes the committed text through
benchmarks/scrape.py: a diff here means the exposition format changed,
and every banked bench column and dashboard reading the old names sees
that change.

Everything is deterministic: fixed counts, a fake timeline clock, an
injected perf-model prediction, and a patched perf-manifest row (the
golden pins the FORMAT of the dtperf series, not the committed perf
numbers, which re-baseline independently).
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

OUT = Path(__file__).resolve().parent

# fixed dtperf manifest rows — both generate.py and the golden test
# patch analysis.perfcheck.manifest_predictions with this exact list
PRED_ROWS = [
    {"entrypoint": "decode_step", "config": "llama3b-v5e",
     "signature": "b64", "bound": "hbm", "predicted_ms": 1.875},
]


class _Clock:
    """Deterministic stand-in for the timeline's perf_counter."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def reset_producers() -> None:
    """Reset every process-global producer the HTTP render reads (the
    same singletons the tier-1 tests isolate against)."""
    from dynamo_tpu.engine import counters as engine_counters
    from dynamo_tpu.engine.counters import (kv_shard_counters,
                                            kv_stream_counters,
                                            persist_counters)
    from dynamo_tpu.fault.counters import counters as fault_counters
    from dynamo_tpu.obs.costs import transfer_costs
    from dynamo_tpu.obs.perfmodel import perf_model
    from dynamo_tpu.obs.timeline import step_timeline

    for c in (engine_counters, persist_counters, kv_stream_counters,
              kv_shard_counters, fault_counters, transfer_costs, perf_model):
        c.reset()
    step_timeline.reset()
    step_timeline._clock = time.perf_counter


def seed_http_metrics():
    """Fixed recording across every producer family; returns the
    seeded ``Metrics`` instance (render via ``render_http``)."""
    from dynamo_tpu.engine.counters import (EngineCounts, kv_shard_counters,
                                            kv_stream_counters,
                                            persist_counters, track_engine)
    from dynamo_tpu.fault.counters import counters as fault_counters
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.obs.costs import transfer_costs
    from dynamo_tpu.obs.perfmodel import perf_model
    from dynamo_tpu.obs.timeline import step_timeline

    reset_producers()

    m = Metrics()
    m.requests[("m1", "completions", "success")] = 3
    m.requests[("m1", "completions", "error")] = 1
    m.inflight["m1"] = 2
    m.tokens_out["m1"] = 64
    m.shed[("m1", "interactive")] = 1
    for v in (0.02, 0.08, 0.4):
        m.ttft["m1"].observe(v)
    for v in (0.004, 0.008, 0.02):
        m.itl["m1"].observe(v)
    m.queue_wait["m1"].observe(0.03)
    m.pre_submit["m1"].observe(0.002)
    for v in (0.0004, 0.003):
        m.emit_lag["m1"].observe(v)
    m.duration[("m1", "success")].observe(1.2)
    m.duration[("m1", "error")].observe(0.01)

    fault_counters.migrations_total = 2
    fault_counters.drains_in_progress = 1
    fault_counters.register_suspect_source(lambda: (7,))

    # what an engine counted: a store as an ``EngineCore`` builds one, the
    # render's to read for as long as ``m`` lives
    ec = EngineCounts()
    track_engine(m, ec)
    # two batched prefills (4 rows, 96 tokens; 2 rows, 64) and one unified
    # dispatch (6 decode rows + 90 prefill tokens), each under a budget of
    # 128; 3 then 1 requests stood ready
    ec.prefill_dispatches_total = 2
    ec.prefill_rows_dispatched = 4 + 2
    ec.prefill_tokens_total = ec.prefill_budget_used = 96 + 64
    ec.prefill_budget_offered = 2 * 128
    ec.prefill_ready_rows_total = 3 + 1
    ec.unified_dispatches_total = 1
    ec.unified_decode_rows = 6
    ec.unified_prefill_tokens = 90
    ec.unified_budget_offered = 128
    ec.unified_budget_used = 6 + 90
    ec.prefill_programs_total = 3       # two batched shapes and the unified
    ec.decode_dispatches_total = 2
    ec.decode_rows_dispatched_total = 12 + 11
    ec.requests_finished_total = 2
    ec.requests_cut_short_total = 1
    ec.first_tokens_total = 1
    ec.first_token_seconds_total = 0.125
    ec.turn_wait_seconds_total = 0.0625
    ec.prefill_span_seconds_total = 0.03125
    ec.ahead_dispatches_total = 5
    ec.ahead_discards_total = 1
    ec.pipeline_drains_total = 2
    ec.operand_buffers_total = 440
    ec.outputs_emitted_total = 1200
    ec.emit_hops_total = 40
    ec.prompt_tokens_admitted_total = 1000
    ec.prompt_tokens_cached_total = 768
    ec.prompt_blocks_admitted_total = 128
    ec.prompt_blocks_reused_total = 96
    ec.attn_context_tokens_total = 48000
    ec.attn_selected_tokens_total = 4096
    ec.attn_fetched_tokens_total = 21000
    ec.index_keys_table_total = 2359296
    ec.index_keys_read_total = 614400
    ec.prefill_masked_tokens_total = 232
    ec.moe_router_picks_total = 4608
    ec.moe_held_picks_total = 576
    ec.moe_expert_layer_calls_total = 36
    ec.moe_experts_touched_total = 540
    ec.moe_skip_picks_total = 271
    ec.state_tokens_total = 7200
    ec.state_resets_total = 12
    ec.mesh_tp = ec.mesh_devices = 4
    ec.loop_tokens_total = 300
    ec.loop_passes_total = 1200
    ec.decode_kv_blocks_walked_total = 2400
    ec.decode_kv_blocks_group_bound_total = 4096
    ec.decode_kv_window_blocks_walked_total = 6336
    ec.decode_kv_window_blocks_span_total = 143616
    ec.window_layers = 6
    ec.sliding_window = 1024
    ec.cache_layers = 192
    ec.kv_bytes_per_token = 1572864
    ec.state_layers = 6
    ec.state_bytes_per_slot = 26050560
    ec.prefix_reuse = 0
    persist_counters.record_restore(2, 32)
    persist_counters.record_miss()
    persist_counters.record_spill(4096)
    persist_counters.set_resident(8192)
    kv_stream_counters.record_session()
    kv_stream_counters.record_layer(2048, 0.002, hidden=True)
    kv_stream_counters.record_layer(2048, 0.002, hidden=False)
    kv_shard_counters.record_scatter(0.3, fan_out=4)
    kv_shard_counters.record_scatter(3.0, fan_out=4)
    kv_shard_counters.record_partial_gather()
    kv_shard_counters.set_generation(2)
    kv_shard_counters.set_shard_size(0, 128, 32)
    kv_shard_counters.set_shard_size(1, 120, 30)
    transfer_costs.record("prefill-0", "decode-0", "dcn", 5_000_000, 0.02)
    transfer_costs.record("prefill-0", "decode-0", "dcn", 5_000_000, 0.025)
    transfer_costs.record("decode-0", "decode-0", "ici", 1_000_000, 0.001)

    # two busy steps at virtual time, one prefill ("step") and one decode:
    # 2 ms host_build, 10 ms dispatch, 1 ms readback, 0.5 ms host_post each
    clock = _Clock()
    step_timeline._clock = clock
    for kind in ("step", "decode_multi"):
        step_timeline.begin("host_build")
        clock.advance(0.002)
        step_timeline.enter("dispatch", kind=kind)
        clock.advance(0.010)
        step_timeline.enter("readback")
        clock.advance(0.001)
        step_timeline.enter("host_post")
        clock.advance(0.0005)
        step_timeline.end()

    # one already-priced perf-model entry: reconcile() joins it with the
    # timeline's measured "step" seconds without tracing anything
    perf_model._entries["step"] = {
        "fn": None, "args": (), "kw": {}, "statics": {},
        "predicted": {"predicted": {"total_ms": 1.25}},
    }
    return m


def render_http() -> str:
    """Seed + render the HTTP surface with the perf-manifest rows
    pinned to PRED_ROWS."""
    from dynamo_tpu.analysis import perfcheck

    m = seed_http_metrics()
    orig = perfcheck.manifest_predictions
    perfcheck.manifest_predictions = lambda: [dict(r) for r in PRED_ROWS]
    try:
        return m.render()
    finally:
        perfcheck.manifest_predictions = orig


def render_components() -> str:
    """Seed + render the standalone metrics component."""
    from dynamo_tpu.components.metrics import PrometheusMetricsCollector
    from dynamo_tpu.llm.kv_router.scheduler import WorkerMetrics

    c = PrometheusMetricsCollector()
    c.on_worker_metrics(WorkerMetrics(
        worker_id=0, request_active_slots=3, request_total_slots=8,
        kv_active_blocks=96, kv_total_blocks=256,
        num_requests_waiting=1, updated_at=0.0))
    c.on_worker_metrics(WorkerMetrics(
        worker_id=1, request_active_slots=5, request_total_slots=8,
        kv_active_blocks=192, kv_total_blocks=256,
        num_requests_waiting=0, updated_at=0.0))
    for _ in range(3):
        c.on_hit_rate_event(0, 10, 7)
    c.on_hit_rate_event(1, 8, 2)
    return c.render()


def main() -> None:
    (OUT / "render_http.txt").write_text(render_http())
    (OUT / "render_components.txt").write_text(render_components())
    reset_producers()
    for name in ("render_http.txt", "render_components.txt"):
        print(f"wrote {OUT / name}")


if __name__ == "__main__":
    main()
