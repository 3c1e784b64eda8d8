"""The serving benchmark harness runs end-to-end against the echo engine."""

import json
import subprocess
import sys
from pathlib import Path


def test_serve_bench_echo_mode():
    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "benchmarks/serve_bench.py", "--spawn-echo",
         "--isl", "32", "--osl", "8", "--concurrency", "1,2",
         "--requests-per-conc", "2"],
        capture_output=True, text=True, timeout=240, cwd=str(repo),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    summary = lines[-1]
    assert summary["metric"] == "serve_output_tok_s"
    assert summary["value"] > 0
    levels = lines[:-1]
    assert [l["concurrency"] for l in levels] == [1, 2]
    assert all(l["ttft_p50_ms"] >= 0 for l in levels)



def test_serve_bench_native_mode():
    """--native boots the REAL engine behind HttpService and the sweep
    counts actual generated tokens (full-coverage detok vocab)."""
    import os

    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "benchmarks/serve_bench.py", "--native", "tiny",
         "--isl", "32", "--osl", "8", "--concurrency", "1",
         "--requests-per-conc", "2"],
        capture_output=True, text=True, timeout=420, cwd=str(repo),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert lines[-1]["metric"] == "serve_output_tok_s"
    assert lines[-1]["value"] > 0  # real engine really streamed tokens
    assert lines[0]["ttft_p50_ms"] > 0


def test_bench_router_smoke():
    """KV-routing A/B harness boots the real graph with 2 replicas and
    emits its comparison JSON (tiny workload; the ratio itself is
    hardware-dependent and not asserted)."""
    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "benchmarks/bench_router.py", "--users", "2",
         "--turns", "2", "--prefix-tokens", "96", "--turn-tokens", "32",
         "--workers", "2"],
        capture_output=True, text=True, timeout=420, cwd=str(repo),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert lines[-1]["metric"] == "kv_router_ttft_speedup"
    assert {l["mode"] for l in lines[:-1]} == {"random", "kv"}
    assert all(l["ttft_mean_ms"] > 0 for l in lines[:-1])


def test_bench_offload_smoke():
    """Host-offload A/B harness runs and actually exercises the host
    tier (blocks stored AND restored) on a tiny eviction-pressure
    workload."""
    repo = Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [sys.executable, "benchmarks/bench_offload.py", "--users", "4",
         "--turns", "3", "--prefix-tokens", "96", "--turn-tokens", "32"],
        capture_output=True, text=True, timeout=420, cwd=str(repo),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    assert lines[-1]["metric"] == "kv_offload_ttft_speedup"
    by_mode = {l["mode"]: l for l in lines[:-1]}
    assert by_mode["host_offload"]["host_blocks_stored"] > 0
    assert by_mode["host_offload"]["host_blocks_restored"] > 0
    assert by_mode["device_only"]["host_blocks_restored"] == 0
