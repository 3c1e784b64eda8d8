"""dtspan — the request-tracing plane.

Zero-dependency observability for the five-process serving path:

- ``obs.tracing``: trace/span core with contextvar propagation, wire
  inject/extract helpers, and a bounded per-process ring-buffer
  collector.  Near-zero cost when disabled (one module-bool check, no
  allocation on the token path).
- ``obs.timeline``: the engine step timeline — per-phase wall-time
  attribution for ``EngineCore.step`` (host scheduling, upload, jitted
  dispatch, readback, post-processing), split into prefill, decode and
  mixed steps.  The counters are always on (about twenty clock reads and
  two small dicts per busy step); under a ``jax.profiler`` session every
  phase is also one ``dyn.<phase>`` event on the engine thread, and with
  no session open that costs one flag test per phase.
- ``obs.costs``: measured KV-transfer cost tables (EWMA per
  (src, dst, path)) fed by spans around ICI/DCN transfers and persist
  restores — the routing input NetKV-style transfer-aware disagg
  needs.  Never-observed edges fall back to the ``obs.topology``
  bandwidth prior instead of a cold miss.
- ``obs.topology``: the versioned per-topology hardware constants
  table (v5e peaks, ICI/DCN link bandwidths) shared with the dtperf
  lint plane; the committed perf manifest pins its version.
- ``obs.perfmodel``: runtime reconciliation of the dtperf roofline —
  engine dispatch sites offer their live signatures, predictions are
  traced lazily, and ``/metrics`` exports the predicted-vs-measured
  model-error gauge per dispatch kind.
- ``obs.export``: Chrome trace-event JSON (Perfetto-loadable) export,
  including the predicted-vs-measured dispatch counter track.
"""

from dynamo_tpu.obs.tracing import (  # noqa: F401
    attach,
    collector,
    current,
    detach,
    enable,
    enabled,
    extract,
    inject,
    set_process,
    start_span,
)
from dynamo_tpu.obs.timeline import step_timeline  # noqa: F401
from dynamo_tpu.obs.costs import transfer_costs  # noqa: F401
from dynamo_tpu.obs.perfmodel import perf_model  # noqa: F401
from dynamo_tpu.obs.export import chrome_trace  # noqa: F401
