"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2,
rehearsal 1): the same phases, children and checks at tiny widths, with
kernels in interpret mode.  It proves the script's paths, arguments and
control flow — what it proves about the chip only a chip run can say."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _smoke(*args, cwd=REPO):
    # the sandbox's own environment: JAX_PLATFORMS=cpu, no accelerator
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(Path(cwd) / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=str(cwd), env=env)
    out.lines = out.stdout.strip().splitlines()
    return out


@pytest.fixture(scope="module")
def tiny():
    """One --tiny run shared by the tests below (a fixture's time is not
    a test's: the run costs more than the per-test budget)."""
    return _smoke("--tiny")


def test_tiny_rehearsal_passes(tiny):
    assert tiny.returncode == 0, tiny.stdout[-3000:] + tiny.stderr[-2000:]
    assert json.loads(tiny.lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_tiny_phase_order_and_parent_off_jax(tiny):
    """Each phase is a child, one after the other; the parent asserts it
    never imported jax right before its last line (a parent that touched
    jax would hold the chip the server child needs)."""
    ran = [l.split("phase ")[1] for l in tiny.lines if "] phase " in l]
    assert ran == ["device", "checkpoint", "serve", "serve-warm", "kernels"]
    assert tiny.lines[-2].endswith(
        "phases run: device checkpoint serve serve-warm kernels")
    src = (REPO / "chip_smoke.py").read_text()
    assert 'assert "jax" not in sys.modules' in src
    head = src[:src.index("def _child_device")]
    assert "import jax" not in head, "module level must stay off jax"


def test_tiny_reports_what_the_server_ran(tiny):
    """Set-up apart from serving, the attention implementation per phase,
    the native library, cache hits on the second start, every kernel."""
    text = tiny.stdout
    assert '"decode": "xla (backend is cpu)"' in text
    assert "native: native v" in text or "native: python" in text
    assert "set-up" in text and "serving" in text
    warm = [l for l in tiny.lines if l.startswith("  serve-warm: compile")]
    assert warm and " 0 hits" not in warm[0]
    kernels = [l for l in tiny.lines if l.startswith("  kernel ")]
    # nine lines of the GQA kernels and the int8 matmul, then the sparse
    # and the masked latent-attention kernels, the indexer's decode scores,
    # the latent cache's writes,
    # the three recurrent states' decode steps, the selective scan of a
    # prefill chunk and the experts' grouped matmul
    assert len(kernels) == 18 and all(
        l.endswith("PASS") and "interpret=True" in l for l in kernels)
    assert any("sparse_latent" in l for l in kernels)
    assert any("masked_latent" in l for l in kernels)
    assert any("latent_write_rows" in l for l in kernels)
    assert any(l.split()[1] == "experts" for l in kernels)
    assert {"state_step", "ssm_step", "sel_step", "sel_scan"} <= {
        l.split()[1] for l in kernels}


def test_device_check_fails_on_a_cpu_machine():
    """Without --tiny the device check is on: no accelerator means a
    non-zero exit and "ok": false before any other phase — never a
    pass, and no 2.5 GB checkpoint written first."""
    out = _smoke()
    assert out.returncode != 0
    last = json.loads(out.lines[-1])
    assert last["ok"] is False and last["failed"] == "device"
    assert last["device"]["platform"] == "cpu"
    assert any(l.endswith("phases run: device") for l in out.lines)
    assert not (REPO / ".cache" / "chip_smoke_ckpt").exists()


def test_alone_in_a_directory_fails(tmp_path):
    """The script without the program proves nothing: it must fail."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    out = _smoke("--tiny", cwd=tmp_path)
    assert out.returncode != 0
    assert json.loads(out.lines[-1])["ok"] is False
