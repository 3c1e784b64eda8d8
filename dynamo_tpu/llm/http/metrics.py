"""HTTP service metrics in Prometheus text exposition format.

Reference parity: lib/llm/src/http/service/metrics.rs:36-46 (request
counters by model/endpoint/status, inflight gauge with RAII guard).
No prometheus client dependency — the text format is trivial to emit.

Every metric name comes from the committed registry
(``obs/metric_names.py``); the dtmet lint plane
(``analysis/metcheck.py``) statically extracts each ``# TYPE`` and
sample line below and audits the producer -> renderer -> scraper
chain, so a renamed or dropped series fails ``lint --metrics`` instead
of silently zeroing a bench column.
"""

from __future__ import annotations

import bisect
import time
from collections import defaultdict
from typing import Iterator

from dynamo_tpu.engine.counters import (engine_totals, kv_shard_counters,
                                        kv_stream_counters, persist_counters)
from dynamo_tpu.fault.counters import counters as fault_counters
from dynamo_tpu.obs.costs import transfer_costs
from dynamo_tpu.obs.metric_names import EngineMetric as EM
from dynamo_tpu.obs.metric_names import FaultMetric as FM
from dynamo_tpu.obs.metric_names import HttpMetric as HM
from dynamo_tpu.obs.metric_names import KvShardMetric as SHM
from dynamo_tpu.obs.metric_names import KvStreamMetric as STM
from dynamo_tpu.obs.metric_names import KvTransferMetric as KM
from dynamo_tpu.obs.metric_names import PREFILL_FAMILY, REQUEST_FAMILY
from dynamo_tpu.obs.metric_names import PerfMetric as PM
from dynamo_tpu.obs.perfmodel import perf_model
from dynamo_tpu.obs.timeline import CLASSES, PHASES, step_timeline

# seconds; TTFT and whole-request durations share one ladder
_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
# finer ladder for per-token gaps — ITL sits well under the request
# ladder's first bound on warm decode
_ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5)


class Histogram:
    """Minimal Prometheus histogram (cumulative buckets + sum + count)."""

    def __init__(self, buckets: tuple = _BUCKETS) -> None:
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # last = +Inf
        self.total = 0.0
        self.n = 0

    def observe(self, v: float) -> None:
        # first bucket with bound >= v; past the ladder = the +Inf slot
        self.counts[bisect.bisect_left(self.buckets, v)] += 1
        self.total += v
        self.n += 1

    def render(self, name: str, labels: str) -> Iterator[str]:
        cum = 0
        for b, c in zip(self.buckets, self.counts):
            cum += c
            yield f'{name}_bucket{{{labels},le="{b}"}} {cum}'
        yield f'{name}_bucket{{{labels},le="+Inf"}} {self.n}'
        yield f'{name}_sum{{{labels}}} {round(self.total, 6)}'
        yield f'{name}_count{{{labels}}} {self.n}'


def _render_counts(lines: list[str], family, totals) -> None:
    """The ``# TYPE`` and sample line of every named entry of ``family``."""
    for entry in family:
        if entry.name:
            value = entry.value(totals)
            if isinstance(value, float):
                value = round(value, 6)
            lines.append(f"# TYPE {entry.name} {entry.kind}")
            lines.append(f"{entry.name} {value}")


class Metrics:
    def __init__(self) -> None:
        # (model, endpoint, status) -> count
        self.requests: dict[tuple[str, str, str], int] = defaultdict(int)
        # model -> inflight
        self.inflight: dict[str, int] = defaultdict(int)
        self.tokens_out: dict[str, int] = defaultdict(int)
        self.ttft: dict[str, Histogram] = defaultdict(Histogram)
        # per-token gap after the first token (the streaming-latency SLO
        # metric TTFT says nothing about); multi-token emissions spread
        # the emission gap evenly across their tokens
        self.itl: dict[str, Histogram] = defaultdict(
            lambda: Histogram(_ITL_BUCKETS))
        # submit -> slot admission wait inside the engine (from
        # EngineRequest.queue_wait_s via Context annotations)
        self.queue_wait: dict[str, Histogram] = defaultdict(Histogram)
        # the front end's two ends (docs/observability.md, "A request's
        # stages"): handler entry -> engine submit (parse, template,
        # tokenise, admission control, the hop), and an output's emit on
        # the engine thread -> its chunk written to the socket: time work
        # waited for the event loop, measured where it waits
        self.pre_submit: dict[str, Histogram] = defaultdict(Histogram)
        self.emit_lag: dict[str, Histogram] = defaultdict(
            lambda: Histogram(_ITL_BUCKETS))
        # duration keyed by (model, status): near-zero error/disconnect
        # requests must not pull the success series' percentiles down
        self.duration: dict[tuple[str, str], Histogram] = defaultdict(Histogram)
        # (model, priority) -> requests shed by admission control (429)
        self.shed: dict[tuple[str, str], int] = defaultdict(int)
        # live TTFT taps (seconds) — the admission controller subscribes
        # here so its deadline estimates track the serving latency plane
        self.ttft_listeners: list = []

    def guard(self, model: str, endpoint: str) -> "InflightGuard":
        return InflightGuard(self, model, endpoint)

    def render(self) -> str:
        lines: list[str] = []
        lines.append(f"# TYPE {HM.REQUESTS_TOTAL} counter")
        for (model, endpoint, status), n in sorted(self.requests.items()):
            lines.append(
                f'{HM.REQUESTS_TOTAL}{{model="{model}",endpoint="{endpoint}",status="{status}"}} {n}'
            )
        lines.append(f"# TYPE {HM.INFLIGHT_REQUESTS} gauge")
        for model, n in sorted(self.inflight.items()):
            lines.append(f'{HM.INFLIGHT_REQUESTS}{{model="{model}"}} {n}')
        lines.append(f"# TYPE {HM.OUTPUT_TOKENS_TOTAL} counter")
        for model, n in sorted(self.tokens_out.items()):
            lines.append(f'{HM.OUTPUT_TOKENS_TOTAL}{{model="{model}"}} {n}')
        lines.append(f"# TYPE {HM.ADMISSION_SHED_TOTAL} counter")
        for (model, priority), n in sorted(self.shed.items()):
            lines.append(
                f'{HM.ADMISSION_SHED_TOTAL}{{model="{model}",priority="{priority}"}} {n}'
            )
        lines.append(f"# TYPE {HM.TTFT_SECONDS} histogram")
        for model, h in sorted(self.ttft.items()):
            lines.extend(h.render(HM.TTFT_SECONDS, f'model="{model}"'))
        lines.append(f"# TYPE {HM.INTER_TOKEN_SECONDS} histogram")
        for model, h in sorted(self.itl.items()):
            lines.extend(h.render(HM.INTER_TOKEN_SECONDS,
                                  f'model="{model}"'))
        lines.append(f"# TYPE {HM.QUEUE_WAIT_SECONDS} histogram")
        for model, h in sorted(self.queue_wait.items()):
            lines.extend(h.render(HM.QUEUE_WAIT_SECONDS,
                                  f'model="{model}"'))
        lines.append(f"# TYPE {HM.PRE_SUBMIT_SECONDS} histogram")
        for model, h in sorted(self.pre_submit.items()):
            lines.extend(h.render(HM.PRE_SUBMIT_SECONDS,
                                  f'model="{model}"'))
        lines.append(f"# TYPE {HM.EMIT_LAG_SECONDS} histogram")
        for model, h in sorted(self.emit_lag.items()):
            lines.extend(h.render(HM.EMIT_LAG_SECONDS,
                                  f'model="{model}"'))
        lines.append(f"# TYPE {HM.REQUEST_SECONDS} histogram")
        for (model, status), h in sorted(self.duration.items()):
            lines.extend(h.render(
                HM.REQUEST_SECONDS,
                f'model="{model}",status="{status}"'))
        # fault plane (process-global): migrations performed, drains live,
        # instances currently suspect per the health probes
        lines.append(f"# TYPE {FM.MIGRATIONS_TOTAL} counter")
        lines.append(f"{FM.MIGRATIONS_TOTAL} "
                     f"{fault_counters.migrations_total}")
        lines.append(f"# TYPE {FM.DRAINS_IN_PROGRESS} gauge")
        lines.append(f"{FM.DRAINS_IN_PROGRESS} "
                     f"{fault_counters.drains_in_progress}")
        lines.append(f"# TYPE {FM.SUSPECT_INSTANCES} gauge")
        lines.append(f"{FM.SUSPECT_INSTANCES} "
                     f"{fault_counters.suspect_instances()}")
        # what the engines of this process counted (obs/metric_names.py
        # declares each): prefill batching and the unified dispatch here ...
        totals = engine_totals()
        _render_counts(lines, PREFILL_FAMILY, totals)
        # persistent prefix-cache tier (llm/kv/persist.py): blocks/tokens
        # restored from disk instead of re-prefilled, spill volume, and
        # the store's current footprint
        lines.append(f"# TYPE {EM.PERSIST_HITS_TOTAL} counter")
        lines.append(f"{EM.PERSIST_HITS_TOTAL} "
                     f"{persist_counters.hits_total}")
        lines.append(f"# TYPE {EM.PERSIST_MISSES_TOTAL} counter")
        lines.append(f"{EM.PERSIST_MISSES_TOTAL} "
                     f"{persist_counters.misses_total}")
        lines.append(f"# TYPE {EM.PERSIST_RESTORED_TOKENS_TOTAL} counter")
        lines.append(f"{EM.PERSIST_RESTORED_TOKENS_TOTAL} "
                     f"{persist_counters.restored_tokens_total}")
        lines.append(f"# TYPE {EM.PERSIST_SPILL_BYTES_TOTAL} counter")
        lines.append(f"{EM.PERSIST_SPILL_BYTES_TOTAL} "
                     f"{persist_counters.spill_bytes_total}")
        lines.append(f"# TYPE {EM.PERSIST_RESIDENT_BYTES} gauge")
        lines.append(f"{EM.PERSIST_RESIDENT_BYTES} "
                     f"{persist_counters.resident_bytes}")
        # streamed KV handoff (llm/kv/stream.py): layer frames shipped
        # while prefill still computed, and how often the stream fell
        # back to the blocking whole-cache push
        lines.append(f"# TYPE {STM.SESSIONS_TOTAL} counter")
        lines.append(f"{STM.SESSIONS_TOTAL} "
                     f"{kv_stream_counters.sessions_total}")
        lines.append(f"# TYPE {STM.LAYERS_SENT_TOTAL} counter")
        lines.append(f"{STM.LAYERS_SENT_TOTAL} "
                     f"{kv_stream_counters.layers_sent_total}")
        lines.append(f"# TYPE {STM.BYTES_TOTAL} counter")
        lines.append(f"{STM.BYTES_TOTAL} "
                     f"{kv_stream_counters.bytes_total}")
        lines.append(f"# TYPE {STM.FALLBACKS_TOTAL} counter")
        lines.append(f"{STM.FALLBACKS_TOTAL} "
                     f"{kv_stream_counters.fallbacks_total}")
        lines.append(f"# TYPE {STM.OVERLAP_RATIO} gauge")
        lines.append(f"{STM.OVERLAP_RATIO} "
                     f"{round(kv_stream_counters.overlap_ratio, 6)}")
        # sharded control plane (llm/kv_router/shards/): scatter rounds,
        # partial gathers (a shard missed its deadline or answered behind
        # the generation fence), fan-out latency, per-shard index gauges
        sc = kv_shard_counters
        lines.append(f"# TYPE {SHM.SCATTERS_TOTAL} counter")
        lines.append(f"{SHM.SCATTERS_TOTAL} {sc.scatters_total}")
        lines.append(f"# TYPE {SHM.GATHER_PARTIAL_TOTAL} counter")
        lines.append(f"{SHM.GATHER_PARTIAL_TOTAL} "
                     f"{sc.gather_partial_total}")
        lines.append(f"# TYPE {SHM.GENERATION} gauge")
        lines.append(f"{SHM.GENERATION} {sc.generation}")
        lines.append(f"# TYPE {SHM.LAST_FAN_OUT} gauge")
        lines.append(f"{SHM.LAST_FAN_OUT} {sc.last_fan_out}")
        lines.append(f"# TYPE {SHM.FANOUT_LATENCY_MS} histogram")
        for edge, count in zip(sc.FANOUT_BUCKETS_MS,
                               sc.fanout_bucket_counts):
            lines.append(
                f'{SHM.FANOUT_LATENCY_MS}_bucket{{le="{edge}"}} {count}')
        lines.append(f'{SHM.FANOUT_LATENCY_MS}_bucket{{le="+Inf"}} '
                     f"{sc.scatters_total}")
        lines.append(f"{SHM.FANOUT_LATENCY_MS}_sum "
                     f"{round(sc.fanout_ms_sum, 6)}")
        lines.append(f"{SHM.FANOUT_LATENCY_MS}_count "
                     f"{sc.scatters_total}")
        if sc.index_blocks:
            lines.append(f"# TYPE {SHM.INDEX_BLOCKS} gauge")
            for shard_id, blocks in sorted(sc.index_blocks.items()):
                lines.append(
                    f'{SHM.INDEX_BLOCKS}{{shard="{shard_id}"}} {blocks}')
            lines.append(f"# TYPE {SHM.RESIDENT_KEYS} gauge")
            for shard_id, keys in sorted(sc.resident_keys.items()):
                lines.append(
                    f'{SHM.RESIDENT_KEYS}{{shard="{shard_id}"}} {keys}')
        # dtspan engine step timeline: per-phase wall attribution plus the
        # headline host bubble (ROADMAP item 3's committed before-number)
        tl = step_timeline.snapshot()
        lines.append(f"# TYPE {EM.STEPS_TOTAL} counter")
        lines.append(f"{EM.STEPS_TOTAL} {tl['steps_total']}")
        lines.append(f"# TYPE {EM.BUSY_STEPS_TOTAL} counter")
        lines.append(f"{EM.BUSY_STEPS_TOTAL} {tl['busy_steps_total']}")
        lines.append(f"# TYPE {EM.STEP_WALL_SECONDS_TOTAL} counter")
        lines.append(f"{EM.STEP_WALL_SECONDS_TOTAL} "
                     f"{round(tl['wall_seconds_total'], 6)}")
        lines.append(f"# TYPE {EM.STEP_PHASE_SECONDS_TOTAL} counter")
        for p in PHASES:
            lines.append(
                f'{EM.STEP_PHASE_SECONDS_TOTAL}{{phase="{p}"}} '
                f"{round(tl['phases'][p], 6)}")
        # busy steps by what they dispatched: prompt processing, token
        # generation, or both in one step — wall, its device-facing
        # part (dispatch -> readback returned), and the two halves a
        # turn's pace is read from: launch (upload + dispatch, and the
        # upload alone) and readback (the host blocked on the device: its
        # slack — and in how many turns it had none: the dispatch it read
        # was done already)
        for name, key in (
                (EM.STEP_CLASS_STEPS_TOTAL, "steps_total"),
                (EM.STEP_CLASS_WALL_SECONDS_TOTAL, "wall_seconds_total"),
                (EM.STEP_CLASS_DEVICE_SECONDS_TOTAL,
                 "device_seconds_total"),
                (EM.STEP_CLASS_LAUNCH_SECONDS_TOTAL,
                 "launch_seconds_total"),
                (EM.STEP_CLASS_UPLOAD_SECONDS_TOTAL,
                 "upload_seconds_total"),
                (EM.STEP_CLASS_READBACK_SECONDS_TOTAL,
                 "readback_seconds_total"),
                (EM.STEP_CLASS_READY_READBACKS_TOTAL,
                 "ready_readbacks_total")):
            lines.append(f"# TYPE {name} counter")
            for c in CLASSES:
                lines.append(f'{name}{{class="{c}"}} '
                             f"{round(tl[f'{c}_{key}'], 6)}")
        # did the device wait?  A launch is starved when the dispatch
        # before it had finished by the time it was issued; lo and hi
        # bracket how long the chip stood with nothing queued.  A replica
        # whose lo grows is host-bound (docs/observability.md)
        lines.append(f"# TYPE {EM.LAUNCHES_TOTAL} counter")
        lines.append(f"{EM.LAUNCHES_TOTAL} {tl['launches_total']}")
        lines.append(f"# TYPE {EM.STARVED_LAUNCHES_TOTAL} counter")
        lines.append(f"{EM.STARVED_LAUNCHES_TOTAL} "
                     f"{tl['starved_launches_total']}")
        lines.append(f"# TYPE {EM.DEVICE_WAIT_SECONDS_TOTAL} counter")
        for b in ("lo", "hi"):
            lines.append(
                f'{EM.DEVICE_WAIT_SECONDS_TOTAL}{{bound="{b}"}} '
                f"{round(tl[f'device_wait_{b}_seconds_total'], 6)}")
        # ... and decode occupancy, request endings, the engine-side TTFT
        # and its stages, dispatch-ahead, what the models counted on the
        # device, the mesh and the cache
        _render_counts(lines, REQUEST_FAMILY, totals)
        lines.append(f"# TYPE {EM.HOST_GAP_MS_PER_TURN} gauge")
        lines.append(f"{EM.HOST_GAP_MS_PER_TURN} "
                     f"{round(tl['host_gap_ms_per_turn'], 6)}")
        # measured KV-transfer costs per (src, dst, path) edge
        costs = transfer_costs.snapshot()
        if costs:
            for name, typ in ((KM.CALLS_TOTAL, "counter"),
                              (KM.BYTES_TOTAL, "counter"),
                              (KM.SECONDS_TOTAL, "counter"),
                              (KM.MBPS, "gauge"),
                              (KM.LATENCY_MS, "gauge")):
                lines.append(f"# TYPE {name} {typ}")
                for (src, dst, path), e in sorted(costs.items()):
                    labels = f'src="{src}",dst="{dst}",path="{path}"'
                    val = {
                        KM.CALLS_TOTAL: e["calls"],
                        KM.BYTES_TOTAL: e["bytes"],
                        KM.SECONDS_TOTAL: round(e["seconds"], 6),
                        KM.MBPS: round(e["ewma_mbps"], 6),
                        KM.LATENCY_MS: round(e["ewma_latency_s"] * 1e3, 6),
                    }[name]
                    lines.append(f"{name}{{{labels}}} {val}")
        # dtperf plane: roofline-predicted step latency per (entrypoint,
        # config) from the committed perf manifest (JSON-only read — no
        # tracing happens here), plus the runtime predicted-vs-measured
        # reconciliation per live dispatch kind
        try:
            from dynamo_tpu.analysis.perfcheck import manifest_predictions

            rows = manifest_predictions()
        except Exception:
            rows = []
        if rows:
            lines.append(f"# TYPE {PM.PREDICTED_STEP_MS} gauge")
            for r in rows:
                labels = (f'entrypoint="{r["entrypoint"]}",'
                          f'config="{r["config"]}",'
                          f'signature="{r["signature"]}",'
                          f'bound="{r["bound"]}"')
                lines.append(
                    f"{PM.PREDICTED_STEP_MS}{{{labels}}} "
                    f"{r['predicted_ms']}")
        recon = perf_model.reconcile()
        if recon:
            for name, field, typ in (
                    (PM.PREDICTED_DISPATCH_MS, "predicted_ms", "gauge"),
                    (PM.MEASURED_DISPATCH_MS, "measured_ms", "gauge"),
                    (PM.DISPATCHES_TOTAL, "dispatches", "counter"),
                    (PM.MODEL_ERROR_RATIO, "error_ratio", "gauge")):
                rendered = [r for r in recon if r.get(field) is not None]
                if not rendered:
                    continue
                lines.append(f"# TYPE {name} {typ}")
                for r in rendered:
                    lines.append(
                        f'{name}{{kind="{r["kind"]}"}} {r[field]}')
        return "\n".join(lines) + "\n"


class InflightGuard:
    """Counts a request as inflight until closed; records final status."""

    def __init__(self, metrics: Metrics, model: str, endpoint: str):
        self._m = metrics
        self.model = model
        self.endpoint = endpoint
        self._status = "error"
        self._t0 = time.monotonic()
        self._saw_first = False
        self._last_tok = 0.0
        self._m.inflight[model] += 1

    def first_token(self) -> None:
        """Record TTFT once, at the first generated-token emission."""
        if not self._saw_first:
            self._saw_first = True
            now = time.monotonic()
            self._last_tok = now
            dt = now - self._t0
            self._m.ttft[self.model].observe(dt)
            for listener in self._m.ttft_listeners:
                listener(dt)

    def tokens(self, k: int) -> None:
        """Record a k-token emission: TTFT on the first, then the
        emission gap spread as k equal inter-token observations (so the
        histogram count tracks tokens, and multi-step decode bursts
        don't read as one slow token)."""
        if k <= 0:
            return
        if not self._saw_first:
            self.first_token()
            k -= 1
            if k <= 0:
                return
        now = time.monotonic()
        per = (now - self._last_tok) / k
        h = self._m.itl[self.model]
        for _ in range(k):
            h.observe(per)
        self._last_tok = now

    def ok(self) -> None:
        self._status = "success"

    def status(self, s: str) -> None:
        self._status = s

    def close(self) -> None:
        self._m.inflight[self.model] -= 1
        self._m.requests[(self.model, self.endpoint, self._status)] += 1
        self._m.duration[(self.model, self._status)].observe(
            time.monotonic() - self._t0)
