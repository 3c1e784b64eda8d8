"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver's dryrun does the same).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.utils import force_cpu_devices

force_cpu_devices(8)

# Persistent XLA compile cache for the suite: dozens of modules compile
# the same tiny-model bucket shapes, but every EngineCore is a fresh jit
# closure, so jax's in-memory cache never hits across tests.  The disk
# cache is keyed by serialized HLO and dedupes those compiles within one
# run (and warm-starts repeat runs) — it shaves minutes off the tier-1
# wall clock without changing what executes.  Same placement as the
# serving entrypoints: JAX_COMPILATION_CACHE_DIR, else <checkout>/.cache/xla.
from dynamo_tpu.utils.compilation_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Every loaded XLA:CPU executable holds memory mappings (a file of
    engine tests leaves ~6,000 behind), a worker keeps its process for some
    thirty files, and the kernel gives a process ``vm.max_map_count`` =
    65,530 of them: past that an ``mmap`` inside XLA's compile, serialise
    or load fails and the worker dies natively, in whichever test compiles
    next (ROADMAP D11 (b); under xdist's loadfile the run then hangs).
    Dropping JAX's in-memory caches at the end of a file gives the mappings
    back (6,004 -> 636 measured); the disk cache keeps the next file warm,
    and an ``EngineCore`` is a fresh jit closure anyway."""
    yield
    import gc

    import jax

    jax.clear_caches()
    gc.collect()


# dtsan runtime sanitizer (docs/static_analysis.md#runtime-sanitizer):
# task-LEAK checking is on by default in tier-1; DYNAMO_SANITIZE=1
# upgrades to the full instrument set, DYNAMO_SANITIZE=0 disables.
from dynamo_tpu.analysis import pytest_sanitizer as _dtsan  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long soak / fault-injection tests excluded from tier-1 "
        "(-m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "no_sanitize: exempt this test from dtsan runtime-sanitizer "
        "failures (leaked tasks / blocking callbacks / unclosed "
        "transports)",
    )
    _dtsan.configure(config)


def pytest_runtest_setup(item):
    _dtsan.begin_test(item)


# ---------------------------------------------------- tier-1 time budget
# Tier-1 runs the whole non-slow suite under one hard wall-clock timeout;
# a single unmarked test creeping towards a minute silently eats the
# budget for everyone.  This guard fails any PASSING test whose call
# phase burns more CPU than the budget unless it is marked
# @pytest.mark.slow — new long tests must opt out of tier-1 explicitly.
# (Failing tests are left alone: the real failure is the signal there.)
#
# The clock is the test's own CPU time (this process and the children it
# waited for), not the wall: tier-1 runs under six xdist workers, and on
# the wall a test pays for its neighbours (the same 4 s kernel test read
# 21.1 s in one whole run and 14.8 s in the next).  CPU seconds are what
# the test costs whoever runs beside it; they exceed its wall time alone
# by 1.3-3x where XLA compiles on several threads or the test runs child
# processes, and move by a third from one loaded run to the next.  Hence
# 60: what one core spends on a minute-long test, the thing to keep out.
# One whole six-worker run with a cold compile cache (PR 31, 450 s; every
# test's figure is the junit property call_cpu_s) read, outside the list
# below, at most 38.1 (test_unified_dispatch.py, test_multihost.py), then
# 30.9 (test_checkpoint.py): a margin of 1.5x.
_CPU_BUDGET_S = float(os.environ.get("DYNAMO_TEST_TIME_BUDGET", "60"))

# Known offenders predating the guard (module-level: any test in these
# files is exempt — several share module-scoped fixtures whose cost lands
# on whichever test runs first).  Burn this list down; do NOT grow it.
# Worst calls in that run and in PR 30's like it (CPU-s):
# test_engine_soak.py 59.0 / 52.1, test_spec_decode.py 44.9 / 31.4,
# test_serve_bench.py 38.1 / 52.7 — each within a bad run of the budget.
# Pruned: test_sampling_extras.py (28.1 / 20.6).
_TIME_BUDGET_GRANDFATHERED_FILES = {
    "test_engine_soak.py",
    "test_serve_bench.py",
    "test_spec_decode.py",
}


def _cpu_seconds() -> float:
    t = os.times()
    return t.user + t.system + t.children_user + t.children_system


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    t0 = _cpu_seconds()
    yield
    item.call_cpu_s = _cpu_seconds() - t0
    # lands in --junitxml, so the numbers above can be read off any run
    item.user_properties.append(("call_cpu_s", round(item.call_cpu_s, 1)))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    cpu_s = getattr(item, "call_cpu_s", 0.0)
    if (
        rep.when == "call"
        and rep.passed
        and cpu_s > _CPU_BUDGET_S
        and item.get_closest_marker("slow") is None
        and os.path.basename(str(item.fspath))
        not in _TIME_BUDGET_GRANDFATHERED_FILES
    ):
        rep.outcome = "failed"
        rep.longrepr = (
            f"{item.nodeid} used {cpu_s:.1f} CPU-seconds — over the "
            f"{_CPU_BUDGET_S:.0f}s tier-1 per-test budget. Mark it "
            "@pytest.mark.slow (excluded from tier-1) or make it cheaper. "
            "Override with DYNAMO_TEST_TIME_BUDGET."
        )
    # dtsan: fail passing tests that leak tasks (and, under
    # DYNAMO_SANITIZE=1, blocking callbacks / unclosed transports /
    # frame-protocol violations)
    _dtsan.check_report(item, call, rep)


def make_tiny_hf_checkpoint(dst, *, vocab_size=128, hidden_size=32,
                            intermediate_size=64, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2,
                            max_position_embeddings=256, seed=0,
                            extra_vocab=("hello", "world")):
    """Shared tiny on-disk HF Llama checkpoint builder (config +
    safetensors + word-level tokenizer.json).  Several suites still
    carry inline copies of this block with suite-specific vocabs —
    prefer this helper for new tests and fold the copies in when their
    vocab expectations allow."""
    import json

    import pytest

    torch = pytest.importorskip("torch")
    from safetensors.torch import save_file
    from tokenizers import Tokenizer
    from tokenizers import models as tkm
    from tokenizers import pre_tokenizers
    from transformers import LlamaConfig, LlamaForCausalLM

    dst.mkdir(parents=True, exist_ok=True)
    hf_cfg = LlamaConfig(
        vocab_size=vocab_size, hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        num_hidden_layers=num_hidden_layers,
        num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads,
        max_position_embeddings=max_position_embeddings,
    )
    torch.manual_seed(seed)
    hf = LlamaForCausalLM(hf_cfg).eval()
    d = hf_cfg.to_dict()
    d["architectures"] = ["LlamaForCausalLM"]
    (dst / "config.json").write_text(json.dumps(d))
    save_file({k: v.contiguous() for k, v in hf.state_dict().items()},
              str(dst / "model.safetensors"))
    n_words = max(vocab_size - 1 - len(extra_vocab), 1)
    vocab = {f"w{i}": i for i in range(n_words)}
    for j, w in enumerate(extra_vocab):
        vocab[w] = n_words + j
    vocab["[UNK]"] = n_words + len(extra_vocab)
    tok = Tokenizer(tkm.WordLevel(vocab=vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(str(dst / "tokenizer.json"))
    return hf
