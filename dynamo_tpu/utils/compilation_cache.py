"""Persistent XLA compilation cache.

Every bench/serve entrypoint pays tens of seconds of XLA compiles on a
fresh process; the reference amortizes this over a long-lived vLLM worker
(lib/runtime/src/worker.rs), but a respawned run cannot.  JAX's
persistent compilation cache makes the SECOND process start warm:
compiles become disk hits.

Call :func:`enable_persistent_cache` once, before the first jit dispatch.
The directory is placed from outside: where ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and this module sets no directory; otherwise
the cache lives at the fixed ``<checkout>/.cache/xla`` (the path is part
of the cache key, so a directory that moves never hits).
Hit/miss logging: the relevant jax loggers are raised to DEBUG so a run's
transcript shows ``Persistent compilation cache hit`` / ``PERSISTENT
COMPILATION CACHE MISS`` lines — a warm start is provable from the log,
not inferred from timing.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

log = logging.getLogger("dynamo_tpu.compile_cache")

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".cache", "xla",
)

__all__ = ["enable_persistent_cache", "CACHE_DIR"]


def enable_persistent_cache() -> Optional[str]:
    """Configure jax's persistent compilation cache; returns the dir in
    use, or None when it could not be enabled (unwritable dir — the run
    proceeds cold rather than dying)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError:
            log.warning(
                "cannot create XLA cache dir %s; compiles stay cold", path)
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # cache EVERYTHING: the default thresholds skip sub-second compiles,
    # but a serving boot is death by dozens of small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # surface hit/miss lines in transcripts (jax logs them at DEBUG)
    for name in ("jax._src.compilation_cache", "jax._src.compiler"):
        logging.getLogger(name).setLevel(logging.DEBUG)
    log.info("persistent XLA compilation cache: %s", path)
    return path
