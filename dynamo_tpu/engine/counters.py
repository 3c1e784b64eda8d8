"""Process-global counters of the engine plane.

Same dependency-free idiom as ``dynamo_tpu/fault/counters.py``: the engine
layer records, the llm layer (http/metrics.py render) and the benchmarks
read — no import cycles.

What an ``EngineCore`` counts is declared in ``obs/metric_names.py``
(``ENGINE_COUNTS``: name, type, ``metrics()`` key and help of each count):
``EngineCounts`` is one engine's store of them, written on its thread, and
``engine_totals`` the sum over the engines this process has had, which is
what ``/metrics`` renders.  The persist tier, the streamed handoff and the
sharded control plane each record into a singleton of their own below.
"""

from __future__ import annotations

import threading
import weakref

from dynamo_tpu.obs.metric_names import ENGINE_COUNTS

__all__ = ["EngineCounts", "track_engine", "engine_totals", "reset",
           "PersistCounters", "persist_counters",
           "KvStreamCounters", "kv_stream_counters",
           "KvShardCounters", "kv_shard_counters"]

# attribute -> initial value: every stored entry, then the operands of the
# derived ones that have no entry of their own
_STORED = {e.attr: e.initial for e in ENGINE_COUNTS if e.ratio is None}
for _e in ENGINE_COUNTS:
    for _attr in _e.ratio or ():
        _STORED.setdefault(_attr, 0)
# set at construction and true of one engine: not summed, the newest's shown
_SHAPE = tuple(e.attr for e in ENGINE_COUNTS
               if e.kind == "gauge" and e.ratio is None)
_SUMMED = tuple(attr for attr in _STORED if attr not in _SHAPE)


class EngineCounts:
    """One engine's counts: a plain attribute each, so that counting on the
    engine thread is one attribute add (no lock: one writer, and a reader
    takes whatever whole number stands there)."""

    __slots__ = tuple(_STORED)

    def __init__(self) -> None:
        for attr, initial in _STORED.items():
            setattr(self, attr, initial)


_lock = threading.Lock()       # guards the three below, not the counting
_live: list[EngineCounts] = []
_retired = EngineCounts()      # engines closed or collected, summed
_newest = _retired             # whose shape gauges /metrics shows


def track_engine(owner, counts: EngineCounts):
    """``owner`` (an engine) counts into ``counts`` from now on.  Returns
    the callable that retires them — folds them into the process total, so
    that no counter on ``/metrics`` falls when an engine goes; the owner
    calls it from ``close()``, and it runs by itself if the owner is
    collected unclosed.  Once retired, further counting is not seen."""
    global _newest
    with _lock:
        _live.append(counts)
        _newest = counts
    return weakref.finalize(owner, _retire, counts)


def _retire(counts: EngineCounts) -> None:
    with _lock:
        if counts in _live:     # else reset() has forgotten them
            _live.remove(counts)
            _add(_retired, counts)


def _add(total: EngineCounts, counts: EngineCounts) -> None:
    for attr in _SUMMED:
        setattr(total, attr, getattr(total, attr) + getattr(counts, attr))


def engine_totals() -> EngineCounts:
    """Every engine this process has had, live or gone, summed; the shape
    gauges (mesh, cache) are those of the engine built last."""
    total = EngineCounts()
    with _lock:
        for counts in (_retired, *_live):
            _add(total, counts)
        for attr in _SHAPE:
            setattr(total, attr, getattr(_newest, attr))
    return total


def reset() -> None:
    """Test isolation hook: forget every engine counted so far."""
    global _retired, _newest
    with _lock:
        _live.clear()
        _retired = _newest = EngineCounts()


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
