"""Typed /metrics scrape helpers shared by the bench harnesses.

One parser replaces the ad-hoc ``line.startswith(...)`` loops that used
to live in ``serve_bench.py``/``bench.py``: every name comes from
``dynamo_tpu.obs.metric_names`` (so a rename is one edit, guarded by
the dtmet lint plane), and unknown metrics are skipped with a debug log
— a scrape never KeyErrors on surface drift; drift FAILS in
``dynamo-tpu lint --metrics``, not mid-benchmark.

The ``*_from_text`` stat functions are pure (text in, summary dict
out) so the golden render fixture can round-trip them without a
server; ``serve_bench.py`` keeps thin async HTTP wrappers.
"""

from __future__ import annotations

import logging
import re
from dataclasses import dataclass
from typing import Optional

from dynamo_tpu.obs.metric_names import (
    EngineMetric as EM,
    KvStreamMetric as STM,
    KvTransferMetric as KM,
    PerfMetric as PM,
    metric_names,
)

log = logging.getLogger("benchmarks.scrape")

__all__ = [
    "Sample",
    "MetricsSnapshot",
    "prefill_dispatch_stats_from_text",
    "perf_model_stats_from_text",
]

_LINE_RE = re.compile(
    r"^(?P<name>[A-Za-z_:][A-Za-z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)\s*$"
)
_LABEL_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"')

# histogram child series fold onto the registered base name
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


@dataclass(frozen=True)
class Sample:
    """One exposition line: ``name{labels} value``."""

    name: str
    labels: tuple[tuple[str, str], ...]
    value: float

    def label(self, key: str, default: str = "") -> str:
        for k, v in self.labels:
            if k == key:
                return v
        return default


def _base_name(name: str, known: set[str]) -> Optional[str]:
    if name in known:
        return name
    for suf in _HIST_SUFFIXES:
        if name.endswith(suf) and name[: -len(suf)] in known:
            return name
    return None


class MetricsSnapshot:
    """Parsed Prometheus text exposition, restricted to registry names.

    Tolerant by construction: malformed lines, unparseable values and
    metrics the registry doesn't know are skipped with a debug log —
    never an exception.  Lookups on absent names return the caller's
    default."""

    def __init__(self, samples: list[Sample]):
        self.samples = list(samples)
        self._by_name: dict[str, list[Sample]] = {}
        for s in self.samples:
            self._by_name.setdefault(s.name, []).append(s)

    @classmethod
    def parse(cls, text: str) -> "MetricsSnapshot":
        known = set(metric_names())
        samples: list[Sample] = []
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = _LINE_RE.match(line)
            if m is None:
                log.debug("skipping unparseable metrics line: %r", line)
                continue
            name = _base_name(m.group("name"), known)
            if name is None:
                log.debug("skipping unknown metric %r", m.group("name"))
                continue
            try:
                value = float(m.group("value"))
            except ValueError:
                log.debug("skipping non-numeric sample for %s: %r",
                          name, m.group("value"))
                continue
            labels = tuple(_LABEL_RE.findall(m.group("labels") or ""))
            samples.append(Sample(name, labels, value))
        return cls(samples)

    def names(self) -> set[str]:
        return set(self._by_name)

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def series(self, name: str) -> list[Sample]:
        return list(self._by_name.get(name, []))

    def value(self, name: str, labels: Optional[dict] = None,
              default=None):
        """First sample value for ``name`` whose labels include every
        ``labels`` pair; ``default`` when the series is absent."""
        for s in self._by_name.get(name, []):
            if labels is None or all(
                    s.label(k, None) == v for k, v in labels.items()):
                return s.value
        log.debug("metric %s%s not in snapshot", name, labels or "")
        return default


def prefill_dispatch_stats_from_text(text: str) -> Optional[dict]:
    """Engine-side dispatch summary from one /metrics body: prefill
    batching, unified dispatch, persist tier, step-timeline
    headline, DCN transfer bandwidth and streamed KV handoff.  Returns
    None when no prefill work was recorded (non-dynamo endpoint)."""
    snap = MetricsSnapshot.parse(text)

    def g(name: str, default: float = 0.0) -> float:
        return snap.value(name, default=default)

    dispatches = g(EM.PREFILL_DISPATCHES_TOTAL)
    if not dispatches:
        return None
    out = {
        "prefill_dispatches": int(dispatches),
        "prefill_tokens_per_dispatch": round(
            g(EM.PREFILL_TOKENS_TOTAL) / dispatches, 1),
        "prefill_batch_occupancy": g(EM.PREFILL_BATCH_OCCUPANCY),
        "prefill_budget_utilization": g(EM.PREFILL_BUDGET_UTILIZATION),
    }
    unified = g(EM.UNIFIED_DISPATCHES_TOTAL)
    if unified:
        # unified mixed dispatch engaged: the interleave win per run —
        # each of these turns replaced a decode burst + prefill pair
        out.update({
            "unified_dispatches": int(unified),
            "unified_decode_rows_per_dispatch": round(
                g(EM.UNIFIED_DECODE_ROWS_TOTAL) / unified, 1),
            "unified_prefill_tokens_per_dispatch": round(
                g(EM.UNIFIED_PREFILL_TOKENS_TOTAL) / unified, 1),
            "unified_budget_utilization": g(EM.UNIFIED_BUDGET_UTILIZATION),
        })
    phits = g(EM.PERSIST_HITS_TOTAL)
    pmiss = g(EM.PERSIST_MISSES_TOTAL)
    if phits or pmiss or g(EM.PERSIST_RESIDENT_BYTES):
        # persistent prefix-cache tier engaged (--kv-persist-dir): how
        # many probed block groups restored from disk instead of being
        # re-prefilled, and the store's current footprint
        out.update({
            "persist_hits": int(phits),
            "persist_hit_rate": round(phits / (phits + pmiss), 4)
            if (phits + pmiss) else 0.0,
            "persist_restored_tokens": int(
                g(EM.PERSIST_RESTORED_TOKENS_TOTAL)),
            "persist_spill_bytes": int(g(EM.PERSIST_SPILL_BYTES_TOTAL)),
            "persist_resident_bytes": int(g(EM.PERSIST_RESIDENT_BYTES)),
        })
    host_gap = snap.value(EM.HOST_GAP_MS_PER_TURN)
    if host_gap is not None:
        # the engine step timeline's headline: host wall per dispatching
        # step outside dispatch+readback (ROADMAP item 3 before-number)
        out["host_gap_ms_per_turn"] = round(host_gap, 3)
    # measured DCN transfer bandwidth (EWMA) — keep the max over edges
    # so one scalar summarizes the disagg KV hop
    dcn = [s.value for s in snap.series(KM.MBPS)
           if s.label("path") == "dcn"]
    if dcn:
        out["transfer_mbps_dcn"] = round(max(dcn), 2)
    if g(STM.SESSIONS_TOTAL):
        # layer-wise streamed handoff engaged (DYN_KV_STREAM=1): frames
        # shipped under compute and the measured overlap win
        out.update({
            "kv_stream_sessions": int(g(STM.SESSIONS_TOTAL)),
            "kv_stream_layers_sent": int(g(STM.LAYERS_SENT_TOTAL)),
            "kv_stream_bytes": int(g(STM.BYTES_TOTAL)),
            "kv_stream_fallbacks": int(g(STM.FALLBACKS_TOTAL)),
            "kv_stream_overlap_ratio": round(g(STM.OVERLAP_RATIO), 4),
        })
    return out


# registered reconciliation series -> the per-kind row key the perf
# table and the banked summary expect (the metric name minus family
# prefix, exactly what the old prefix-stripping loop produced)
_PERF_ROW_KEYS = (
    (PM.PREDICTED_DISPATCH_MS, "predicted_dispatch_ms"),
    (PM.MEASURED_DISPATCH_MS, "measured_dispatch_ms"),
    (PM.DISPATCHES_TOTAL, "dispatches_total"),
    (PM.MODEL_ERROR_RATIO, "model_error_ratio"),
)


def perf_model_stats_from_text(text: str) -> Optional[dict]:
    """dtperf predicted-vs-measured reconciliation rows from one
    /metrics body, keyed by dispatch kind.  The static
    ``predicted_step_ms`` manifest rows are excluded — this reads the
    runtime loop only.  Returns None when no dispatch ran."""
    snap = MetricsSnapshot.parse(text)
    rows: dict[str, dict] = {}
    for name, key in _PERF_ROW_KEYS:
        for s in snap.series(name):
            kind = s.label("kind")
            if kind:
                rows.setdefault(kind, {})[key] = s.value
    rows = {k: v for k, v in rows.items() if v.get("dispatches_total")}
    return rows or None
