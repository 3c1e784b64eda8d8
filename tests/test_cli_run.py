"""CLI `run` end-to-end: the primary user command's non-server inputs
(text:, stdin, batch:) in a subprocess exactly as a user invokes it,
against out=echo and the real out=tpu engine on a tiny checkpoint."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    """A tiny on-disk HF checkpoint (config + safetensors + tokenizer)."""
    from tests.conftest import make_tiny_hf_checkpoint

    src = tmp_path_factory.mktemp("cli_model") / "hf"
    make_tiny_hf_checkpoint(src)
    return src


def _run(args, input_text=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    return subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.cli", *args],
        capture_output=True, text=True, timeout=timeout, cwd=str(REPO),
        input=input_text, env=env,
    )


def test_run_text_echo(model_dir):
    out = _run(["run", "in=text:hello world", "out=echo",
                "--model-path", str(model_dir), "--max-tokens", "8"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "hello" in out.stdout


def test_run_stdin_echo(model_dir):
    out = _run(["run", "in=stdin", "out=echo",
                "--model-path", str(model_dir), "--max-tokens", "8"],
               input_text="hello world\nworld hello\n")
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.count("hello") >= 2


def test_run_batch_echo(model_dir, tmp_path):
    f = tmp_path / "prompts.jsonl"
    f.write_text('{"text": "hello world"}\n{"text": "world hello"}\n')
    out = _run(["run", f"in=batch:{f}", "out=echo",
                "--model-path", str(model_dir), "--max-tokens", "8"])
    assert out.returncode == 0, out.stderr[-2000:]
    summary = json.loads(out.stdout.strip().splitlines()[-1])
    assert summary["requests"] == 2
    results = [json.loads(l)
               for l in Path(summary["results"]).read_text().splitlines()]
    assert len(results) == 2 and all(r["output_tokens"] > 0 for r in results)


def test_run_text_tpu_engine(model_dir):
    """The flagship path: load a checkpoint, build the native engine,
    generate — exactly `dynamo-tpu run in=text:... out=tpu`."""
    out = _run(["run", "in=text:hello world", "out=tpu",
                "--model-path", str(model_dir), "--max-tokens", "4",
                "--max-model-len", "64", "--num-blocks", "16",
                "--max-batch-size", "2"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip(), "no generated text on stdout"


def test_the_lookahead_flag_is_refused(model_dir):
    """The lookahead scheduler is gone and its name is still accepted
    (the benchmark passes it): a server must not start in silence on a
    flag that does nothing, so the config refuses it and says what hides
    the host now, and ``run`` exits non-zero."""
    from dynamo_tpu.engine import EngineConfig

    assert EngineConfig().lookahead_dispatch is False
    with pytest.raises(ValueError, match="dispatch-ahead"):
        EngineConfig(lookahead_dispatch=True)
    out = _run(["run", "in=text:hello", "out=tpu", "--model-path",
                str(model_dir), "--max-tokens", "2", "--lookahead-dispatch"])
    assert out.returncode != 0
    assert "dispatch-ahead" in out.stderr
    assert not out.stdout.strip()


def test_worker_config_kv_quant_and_sp_reach_engine(model_dir):
    """The example-graph worker config keys `kv-quant` and
    `sp-prefill-threshold` (multinode-70b/moe.yaml) flow through
    build_engine -> _build_local_engine into the EngineCore."""
    from examples.llm.components.worker import build_engine
    from dynamo_tpu.ops.kv_quant import is_quant

    engine, card = build_engine({
        "engine": "tpu", "model-path": str(model_dir),
        "max-batch-size": 2, "max-model-len": 128, "block-size": 16,
        "num-blocks": 24, "kv-quant": "int8",
        "sp-prefill-threshold": 64, "dp": 2, "tp": 2,
    })
    try:
        core = engine.core
        assert is_quant(core.cache)
        assert core._sp_size == 2  # ring path armed over mesh["data"]
        assert core.config.sp_prefill_threshold == 64
    finally:
        engine.shutdown()


def test_run_out_tpu_without_a_chip_exits_nonzero(model_dir):
    """out=tpu means the chip: with JAX_PLATFORMS unset and no TPU, JAX
    would fall back to the CPU quietly — the CLI must refuse instead.
    (Every other test here sets JAX_PLATFORMS=cpu, which is the caller
    asking for the CPU on purpose.)"""
    env = dict(os.environ, PYTHONPATH=str(REPO))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.cli", "run", "in=text:hello",
         "out=tpu", "--model-path", str(model_dir), "--max-tokens", "2"],
        capture_output=True, text=True, timeout=300, cwd=str(REPO), env=env)
    assert out.returncode != 0
    assert "out=tpu found no TPU" in out.stderr
    assert "startup" not in out.stderr  # refused before loading anything


def test_step_that_cannot_compile_brings_the_server_down(model_dir, tmp_path):
    """A step that raises while building its program is not a
    per-request failure: the requests fail, the engine stops, and the
    server exits non-zero instead of answering every request with an
    error.  The failure is injected the way it happens on the chip — the
    static rule says "pallas" for a kernel the backend cannot compile
    (steered here by telling the dispatch the backend is a TPU while the
    kernels can only lower for one)."""
    import socket
    import time
    import urllib.error
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    shim = (
        "import jax, sys; jax.default_backend = lambda: 'tpu'; "
        "from dynamo_tpu.cli import main; main(sys.argv[1:])")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    log_path = tmp_path / "server.log"  # not a pipe: nobody drains it
    with open(log_path, "w") as log_file:
        proc = subprocess.Popen(
            [sys.executable, "-c", shim, "run", "in=http", "out=tpu",
             "--model-path", str(model_dir), "--max-model-len", "64",
             "--num-blocks", "16", "--max-batch-size", "2",
             "--http-port", str(port)],
            stdout=log_file, stderr=subprocess.STDOUT, cwd=str(REPO), env=env)
    try:
        url = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 120
        while True:
            assert proc.poll() is None, "server died before any request"
            assert time.monotonic() < deadline, "server never came up"
            try:
                urllib.request.urlopen(url + "/health", timeout=2).read()
                break
            except (urllib.error.URLError, OSError):
                time.sleep(0.3)
        req = urllib.request.Request(
            url + "/v1/completions",
            data=json.dumps({"model": model_dir.name, "prompt": "hello world",
                             "max_tokens": 4}).encode(),
            headers={"content-type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=60).read()
        except (urllib.error.URLError, OSError):
            pass  # an error answer or a dropped connection: both fine
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    log = log_path.read_text()
    assert rc not in (0, None), log[-2000:]
    assert "engine step failed" in log
    assert "engine stopped" in log
