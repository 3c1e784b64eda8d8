"""Persistent XLA compilation cache is configured: the bench/serve
entrypoints call enable_persistent_cache() so respawned processes
warm-start from disk instead of recompiling, and the directory is placed
from outside (JAX_COMPILATION_CACHE_DIR) or at one fixed in-checkout path."""

import os
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_dir():
    """The config is process-global: a tmp dir must not outlive the test
    as the suite's cache location — restore whatever the harness
    (conftest) had configured, not None."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_placed_cache_sets_no_dir(tmp_path, monkeypatch,
                                      restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set -> jax reads it itself; the code
    sets no directory (and creates none), only the min-size knobs."""
    import jax

    from dynamo_tpu.utils import compilation_cache as cc

    outside = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    sentinel = str(tmp_path / "whatever-jax-had")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    assert cc.enable_persistent_cache() == outside
    assert jax.config.jax_compilation_cache_dir == sentinel
    assert not os.path.exists(outside)
    # sub-second compiles must be cached too: a serving boot is dozens
    # of small jits, not one big one
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_default_cache_is_fixed_in_checkout_path(monkeypatch,
                                                 restore_cache_dir):
    """Unset -> <checkout>/.cache/xla, the same path in every process
    (the path is part of the cache key), ignored by git."""
    import jax

    from dynamo_tpu.utils import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc.CACHE_DIR == str(REPO / ".cache" / "xla")
    assert cc.enable_persistent_cache() == cc.CACHE_DIR
    assert os.path.isdir(cc.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == cc.CACHE_DIR
    assert ".cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_unwritable_cache_dir_degrades_to_cold(tmp_path, monkeypatch):
    from dynamo_tpu.utils import compilation_cache as cc

    blocker = tmp_path / "file"
    blocker.write_text("not a dir")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # a path that cannot become a directory: run cold, do not die
    monkeypatch.setattr(cc, "CACHE_DIR", str(blocker / "nested"))
    assert cc.enable_persistent_cache() is None


def test_entrypoints_call_enable():
    """The wiring itself: every serving/bench entrypoint routes through
    enable_persistent_cache (source-level check — the call sites run
    on-accelerator paths a CPU test cannot reach end-to-end)."""
    for rel in ("benchmarks/serve_bench.py", "benchmarks/profile_decode.py",
                "dynamo_tpu/cli.py"):
        text = (REPO / rel).read_text()
        assert "enable_persistent_cache" in text, rel
