"""ctypes bindings to the native C++ runtime library (native/).

The native library supplies the hot-path runtime components that the
reference implements in Rust/C (see native/include/dynamo_native.h for the
parity map): the KV prefix index, batched block gather/scatter for the DCN
KV-transfer plane, and the C event-queue API native engines publish KV
events through.

Loading order: prebuilt ``dynamo_tpu/_lib/libdynamo_native.so`` → auto-build
via ``make -C native`` if a toolchain is present → ``None`` (callers fall
back to the pure-Python implementations, which are semantically identical
and covered by the same tests).
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional, Sequence

import numpy as np

log = logging.getLogger("dynamo_tpu.native")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(_REPO, "dynamo_tpu", "_lib", "libdynamo_native.so")
_NATIVE_DIR = os.path.join(_REPO, "native")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_built_here = False

_u64p = ctypes.POINTER(ctypes.c_uint64)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_i32p = ctypes.POINTER(ctypes.c_int32)
_u8p = ctypes.POINTER(ctypes.c_uint8)

EVENT_STORED = 0
EVENT_REMOVED = 1


def _declare(lib: ctypes.CDLL) -> None:
    lib.dyn_index_new.restype = ctypes.c_void_p
    lib.dyn_index_free.argtypes = [ctypes.c_void_p]
    lib.dyn_index_store.argtypes = [ctypes.c_void_p, ctypes.c_uint64, _u64p, ctypes.c_size_t]
    lib.dyn_index_remove.argtypes = [ctypes.c_void_p, ctypes.c_uint64, _u64p, ctypes.c_size_t]
    lib.dyn_index_remove_worker.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.dyn_index_clear.argtypes = [ctypes.c_void_p]
    lib.dyn_index_num_blocks.argtypes = [ctypes.c_void_p]
    lib.dyn_index_num_blocks.restype = ctypes.c_uint64
    lib.dyn_index_num_workers.argtypes = [ctypes.c_void_p]
    lib.dyn_index_num_workers.restype = ctypes.c_uint64
    lib.dyn_index_find_matches.argtypes = [
        ctypes.c_void_p, _u64p, ctypes.c_size_t, _u64p, _u32p, ctypes.c_size_t,
    ]
    lib.dyn_index_find_matches.restype = ctypes.c_size_t

    lib.dyn_blocks_gather.argtypes = [
        _u8p, ctypes.c_uint64, _i64p, ctypes.c_size_t, _u8p, ctypes.c_int,
    ]
    lib.dyn_blocks_scatter.argtypes = [
        _u8p, ctypes.c_uint64, _i64p, ctypes.c_size_t, _u8p, ctypes.c_int,
    ]

    lib.dyn_events_new.argtypes = [ctypes.c_size_t]
    lib.dyn_events_new.restype = ctypes.c_void_p
    lib.dyn_events_free.argtypes = [ctypes.c_void_p]
    lib.dyn_events_publish.argtypes = [
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_uint64, _u64p, ctypes.c_size_t,
    ]
    lib.dyn_events_publish.restype = ctypes.c_int
    lib.dyn_events_drain.argtypes = [
        ctypes.c_void_p, _i32p, _u64p, _u64p, ctypes.c_size_t, _u64p, ctypes.c_size_t,
    ]
    lib.dyn_events_drain.restype = ctypes.c_size_t
    lib.dyn_events_dropped.argtypes = [ctypes.c_void_p]
    lib.dyn_events_dropped.restype = ctypes.c_uint64
    lib.dyn_native_version.restype = ctypes.c_char_p


def _try_build() -> bool:
    global _built_here
    if not os.path.isdir(_NATIVE_DIR):
        return False
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR],
            check=True, capture_output=True, timeout=120,
        )
        _built_here = os.path.exists(_LIB_PATH)
        return _built_here
    except (OSError, subprocess.SubprocessError) as e:
        log.debug("native build failed: %s", e)
        return False


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("DYN_DISABLE_NATIVE"):
        return None
    if not os.path.exists(_LIB_PATH) and not _try_build():
        log.info("native library unavailable; using pure-Python fallbacks")
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        _declare(lib)
        _lib = lib
        log.debug("loaded native library %s (v%s)", _LIB_PATH, lib.dyn_native_version().decode())
    except OSError as e:
        log.warning("failed to load native library: %s", e)
    return _lib


def available() -> bool:
    return load() is not None


def describe() -> str:
    """Which implementation is live, for a server's start-up line — the
    fall to the Python implementations is otherwise silent."""
    lib = load()
    if lib is None:
        return "python (native library unavailable)"
    how = "built by make -C native" if _built_here else "found prebuilt"
    return f"native v{lib.dyn_native_version().decode()} ({how})"


def _as_u64(arr: Sequence[int] | np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(arr, dtype=np.uint64)


class NativeKvIndex:
    """Handle to a native dyn_index (see KvIndexer for the Python-facing API)."""

    def __init__(self) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.dyn_index_new()

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.dyn_index_free(self._h)
            self._h = None

    def store(self, worker: int, hashes: Sequence[int]) -> None:
        a = _as_u64(hashes)
        self._lib.dyn_index_store(self._h, worker, a.ctypes.data_as(_u64p), len(a))

    def remove(self, worker: int, hashes: Sequence[int]) -> None:
        a = _as_u64(hashes)
        self._lib.dyn_index_remove(self._h, worker, a.ctypes.data_as(_u64p), len(a))

    def remove_worker(self, worker: int) -> None:
        self._lib.dyn_index_remove_worker(self._h, worker)

    def clear(self) -> None:
        self._lib.dyn_index_clear(self._h)

    @property
    def num_blocks(self) -> int:
        return self._lib.dyn_index_num_blocks(self._h)

    @property
    def num_workers(self) -> int:
        return self._lib.dyn_index_num_workers(self._h)

    def find_matches(self, hashes: Sequence[int]) -> dict[int, int]:
        a = _as_u64(hashes)
        cap = max(16, self.num_workers)
        while True:
            workers = np.empty(cap, dtype=np.uint64)
            scores = np.empty(cap, dtype=np.uint32)
            n = self._lib.dyn_index_find_matches(
                self._h, a.ctypes.data_as(_u64p), len(a),
                workers.ctypes.data_as(_u64p), scores.ctypes.data_as(_u32p), cap,
            )
            if n <= cap:
                return {int(workers[i]): int(scores[i]) for i in range(n)}
            cap = n


def _check_ids(idx: np.ndarray, n_blocks: int) -> None:
    # The native path is a raw memcpy — bounds must be enforced here, where
    # the numpy fallback would have raised an IndexError.
    if len(idx) and (idx.min() < 0 or idx.max() >= n_blocks):
        raise IndexError(f"block id out of range [0, {n_blocks}): {idx.min()}..{idx.max()}")


def blocks_gather(src: np.ndarray, ids: Sequence[int], threads: int = 0) -> np.ndarray:
    """Gather src[ids] (axis 0) into a fresh contiguous array via native memcpy.

    Same semantics regardless of backend: ids are bounds-checked (no
    negative-index wrapping) and a non-contiguous pool falls back to numpy
    fancy indexing rather than copying the whole pool to linearise it.
    """
    lib = load()
    idx = np.ascontiguousarray(ids, dtype=np.int64)
    _check_ids(idx, src.shape[0])
    if lib is None or not src.flags.c_contiguous:
        return np.ascontiguousarray(src[idx])
    out = np.empty((len(idx),) + src.shape[1:], dtype=src.dtype)
    block_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    lib.dyn_blocks_gather(
        src.ctypes.data_as(_u8p), block_bytes,
        idx.ctypes.data_as(_i64p), len(idx), out.ctypes.data_as(_u8p), threads,
    )
    return out


def blocks_scatter(dst: np.ndarray, ids: Sequence[int], src: np.ndarray, threads: int = 0) -> None:
    """Scatter src rows into dst[ids] (axis 0) in place via native memcpy.

    Validation is identical on both backends (shape match, bounds-checked
    ids).  Duplicate ids resolve last-write-wins like numpy — the native
    threaded path would race on duplicates, so they are deduplicated first.
    """
    lib = load()
    idx = np.ascontiguousarray(ids, dtype=np.int64)
    src = np.asarray(src)
    if src.shape != (len(idx),) + dst.shape[1:]:
        raise ValueError(f"scatter shape mismatch: src {src.shape} vs {(len(idx),) + dst.shape[1:]}")
    _check_ids(idx, dst.shape[0])
    if lib is None or not dst.flags.c_contiguous:
        dst[idx] = src
        return
    if len(np.unique(idx)) != len(idx):
        # keep the LAST occurrence of each id (numpy scatter semantics)
        last = {int(b): i for i, b in enumerate(idx)}
        keep = np.fromiter(last.values(), dtype=np.int64)
        idx = idx[keep]
        src = src[keep]
    src = np.ascontiguousarray(src, dtype=dst.dtype)
    block_bytes = dst.dtype.itemsize * int(np.prod(dst.shape[1:], dtype=np.int64))
    lib.dyn_blocks_scatter(
        dst.ctypes.data_as(_u8p), block_bytes,
        idx.ctypes.data_as(_i64p), len(idx), src.ctypes.data_as(_u8p), threads,
    )


class NativeEventQueue:
    """Bounded queue native engines publish KV events into (C bindings parity)."""

    def __init__(self, capacity: int = 1 << 16) -> None:
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.dyn_events_new(capacity)

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.dyn_events_free(self._h)
            self._h = None

    def publish(self, kind: int, parent_hash: int, hashes: Sequence[int]) -> bool:
        a = _as_u64(hashes)
        rc = self._lib.dyn_events_publish(
            self._h, kind, parent_hash, a.ctypes.data_as(_u64p), len(a)
        )
        return rc == 0

    def drain(self, max_events: int = 1024, hashes_cap: int = 1 << 16) -> list[tuple[int, int, list[int]]]:
        kinds = np.empty(max_events, dtype=np.int32)
        parents = np.empty(max_events, dtype=np.uint64)
        hashes = np.empty(hashes_cap, dtype=np.uint64)
        offsets = np.empty(max_events + 1, dtype=np.uint64)
        n = self._lib.dyn_events_drain(
            self._h, kinds.ctypes.data_as(_i32p), parents.ctypes.data_as(_u64p),
            hashes.ctypes.data_as(_u64p), hashes_cap,
            offsets.ctypes.data_as(_u64p), max_events,
        )
        out = []
        for i in range(n):
            lo, hi = int(offsets[i]), int(offsets[i + 1])
            out.append((int(kinds[i]), int(parents[i]), [int(h) for h in hashes[lo:hi]]))
        return out

    @property
    def dropped(self) -> int:
        return self._lib.dyn_events_dropped(self._h)
