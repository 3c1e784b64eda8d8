#!/usr/bin/env python3
"""The quickest proof that dynamo-tpu still starts on the chip.

Drives the main path once — ``python -m dynamo_tpu.cli run in=http out=tpu``
serving Llama-3.2-1B at its published widths and full depth, random
weights made from ``--seed`` — and checks what comes out.  One child
process per phase, one after the other, because a chip belongs to one
process at a time; this parent never imports jax.

  device      what JAX finds (a machine with no TPU fails here, in seconds)
  checkpoint  an HF-layout dir (config.json, bf16 safetensors,
              tokenizer.json) written with numpy only
  serve       the server answers a handful of HTTP requests; its log holds
              no failed step; it is alive until this script stops it
  serve-warm  the same server again: the compile cache now hits
  kernels     every Pallas kernel of the registry runs on the chip at the
              1B geometry and matches its XLA oracle

``--chips 4`` runs only the tensor-parallel path and what it is compared
with: a ``--tp 4`` server, then a ``--tp 1`` server on the same checkpoint
and requests, top-20 logprobs agreeing within LOGPROB_TOL.

``--tiny`` is the CPU rehearsal (tiny widths, JAX_PLATFORMS=cpu, kernels
in interpret mode): the same phases and checks with the device check off.
It proves the script, never the chip.

The last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}`` with the device as the server child reported
it; any failed phase prints ``"ok": false`` and exits non-zero.  Times
printed on earlier lines are notes for sizing a benchmark, not metrics.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
# 2.5 GB: ignored by git, not under chiprun_out/, removed at the end
CKPT_DIR = os.path.join(HERE, ".cache", "chip_smoke_ckpt")

# meta-llama/Llama-3.2-1B config.json, as published
LLAMA_3_2_1B = {
    "architectures": ["LlamaForCausalLM"], "model_type": "llama",
    "vocab_size": 128256, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 16, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 64, "hidden_act": "silu",
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
    "rope_theta": 500000.0,
    "rope_scaling": {"factor": 32.0, "high_freq_factor": 4.0,
                     "low_freq_factor": 1.0,
                     "original_max_position_embeddings": 8192,
                     "rope_type": "llama3"},
    "tie_word_embeddings": True, "attention_bias": False, "mlp_bias": False,
    "bos_token_id": 128000, "eos_token_id": 128001,
    "torch_dtype": "bfloat16",
}
TINY_MODEL = dict(
    LLAMA_3_2_1B, vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
    head_dim=16, max_position_embeddings=512, bos_token_id=510,
    eos_token_id=511)

# serving geometry (ISSUE 22) and the rehearsal's cut of it
REAL = dict(model=LLAMA_3_2_1B, block_size=32, max_model_len=2048,
            max_batch_size=8, chunk=512, long_prompt=1500, shared_prefix=1000,
            kernel=dict(h=32, hk=8, d=64, bs=32, batch=64, m=64, n=512,
                        s=512, t=1024, rows=8, matmul=(64, 2048, 8192)))
TINY = dict(model=TINY_MODEL, block_size=16, max_model_len=256,
            max_batch_size=4, chunk=64, long_prompt=150, shared_prefix=96,
            kernel=dict(h=4, hk=2, d=32, bs=16, batch=2, m=2, n=8,
                        s=16, t=32, rows=2, matmul=(128, 512, 512)))

MAX_TOKENS = 16
LOGPROB_TOL = 0.25    # |Δ logprob| of a token both runs rank in their top 20
BALANCE_TOL = 1.25    # device 0 bytes in use vs the median of the others
DEADLINE_S = 1150     # the driver's limit is 1200 s


class SmokeFailure(Exception):
    pass


def note(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- children ----


def child(name: str, call: str, arg: dict, env: dict, timeout: float) -> dict:
    """Run ``chip_smoke.<call>(arg)`` in a child process; its last stdout
    line is its JSON result.  stderr goes to a log brought back by the
    chip tool."""
    log_path = os.path.join(LOG_DIR, f"{name}.log")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.run(
            [sys.executable, "-c",
             f"import chip_smoke, json, sys; "
             f"chip_smoke.{call}(json.loads(sys.argv[1]))", json.dumps(arg)],
            cwd=HERE, env=env, stdout=subprocess.PIPE, stderr=log,
            text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        note(f"  {line}")
    if proc.returncode != 0 or not lines:
        tail = open(log_path).read()[-3000:]
        raise SmokeFailure(
            f"{name} child exited {proc.returncode}; end of {log_path}:\n{tail}")
    out = json.loads(lines[-1])
    out["seconds"] = round(time.monotonic() - t0, 1)
    return out


def _child_device(arg: dict) -> None:
    import jax

    devs = jax.devices()
    print(json.dumps({"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}))


def _child_checkpoint(arg: dict) -> None:
    """numpy only: no torch model of 1.2B parameters in f32, no network."""
    import numpy as np
    from tokenizers import Tokenizer, models, pre_tokenizers

    cfg, dst = arg["model"], arg["dst"]
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "config.json"), "w") as f:
        json.dump(cfg, f)
    v, h, inter = cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    # the names models/loader.py reads; HF keeps weights [out, in]
    tensors = [("model.embed_tokens.weight", (v, h)),
               ("model.norm.weight", (h,))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        tensors += [
            (p + "input_layernorm.weight", (h,)),
            (p + "post_attention_layernorm.weight", (h,)),
            (p + "self_attn.q_proj.weight", (q, h)),
            (p + "self_attn.k_proj.weight", (kv, h)),
            (p + "self_attn.v_proj.weight", (kv, h)),
            (p + "self_attn.o_proj.weight", (h, q)),
            (p + "mlp.gate_proj.weight", (inter, h)),
            (p + "mlp.up_proj.weight", (inter, h)),
            (p + "mlp.down_proj.weight", (h, inter)),
        ]
    header, off = {"__metadata__": {"format": "pt"}}, 0
    for name, shape in tensors:
        n = 2 * int(np.prod(shape))
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [off, off + n]}
        off += n
    head = json.dumps(header).encode()
    head += b" " * (-len(head) % 8)
    rng = np.random.default_rng(arg["seed"])
    with open(os.path.join(dst, "model.safetensors"), "wb") as f:
        f.write(len(head).to_bytes(8, "little"))
        f.write(head)
        for name, shape in tensors:
            if len(shape) == 1:  # norm gains
                x = np.ones(shape, np.float32)
            else:
                x = rng.standard_normal(shape, dtype=np.float32)
                x *= cfg.get("initializer_range", 0.02)
            # f32 -> bf16 bits, round to nearest even, in place
            u = x.view(np.uint32)
            r = u >> 16
            r &= 1
            r += 0x7FFF
            u += r
            u >>= 16
            u.astype(np.uint16).tofile(f)
    # word-level tokenizer, as tests/conftest.py builds it: token id i
    # is the word "w<i>", so prompts of exact token lengths are plain text
    vocab = {f"w{i}": i for i in range(v - 1)}
    vocab["[UNK]"] = v - 1
    tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="[UNK]"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    tok.save(os.path.join(dst, "tokenizer.json"))
    print(json.dumps({"bytes": off, "params": off // 2}))


def _child_kernels(arg: dict) -> None:
    """Every registered kernel on the device at serving geometry against
    the XLA oracle of ops/paged_attention.py.  Numbers are compared, not
    greedy tokens: random weights give near-flat logits and argmax is
    not stable."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas import registry as reg
    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_mq,
    )
    from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul
    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention,
        ragged_paged_prefill_attention,
    )

    pa = importlib.import_module("dynamo_tpu.ops.paged_attention")
    g, interpret = arg["kernel"], arg["interpret"]
    h, hk, d, bs, n, m = g["h"], g["hk"], g["d"], g["bs"], g["n"], g["m"]
    rng = np.random.default_rng(arg["seed"])
    lens = rng.integers(1, m * bs + 1, size=g["batch"]).astype(np.int32)
    lens[0], lens[-1] = 1, m * bs  # boundaries: one token, full table

    def oracle(fn, *a, **kw):
        """The dispatch pinned to its XLA path, f32 matmuls."""
        os.environ["DYNAMO_DISABLE_PALLAS"] = "1"
        try:
            with jax.default_matmul_precision("highest"):
                return fn(*a, **kw)
        finally:
            del os.environ["DYNAMO_DISABLE_PALLAS"]

    def decode(quant):
        q, cache, layer, bt, sl = reg.probe_decode_inputs(
            g["batch"], h, hk, d, bs, n, m, lens, quant=quant)
        got = paged_decode_attention(q, cache, layer, bt, sl,
                                     interpret=interpret)
        ref = oracle(pa.paged_attention_layer, q[:, None], cache, layer, bt,
                     sl, (sl - 1)[:, None])[:, 0]
        return got, ref

    def mq(quant, s_q=4):
        q, cache, layer, bt, sl, q0 = reg.probe_decode_inputs(
            g["batch"], h, hk, d, bs, n, m, np.maximum(lens, s_q),
            quant=quant, s_q=s_q)
        got = paged_decode_attention_mq(q, cache, layer, bt, sl, q0,
                                        interpret=interpret)
        pos = q0[:, None] + jnp.arange(s_q, dtype=jnp.int32)[None]
        return got, oracle(pa.paged_attention_layer, q, cache, layer, bt,
                           sl, pos)

    def prefill(quant):
        a = reg.probe_prefill_inputs(1, g["s"], h, hk, d, bs, n, m,
                                     quant=quant)
        got = paged_prefill_attention(*a, interpret=interpret)
        return got, oracle(pa.prefill_attention, *a, prefix_blocks=1)

    def ragged(quant):
        t, rows = g["t"], g["rows"]
        a = reg.probe_ragged_inputs(t, rows, h, hk, d, bs, n, m, quant=quant)
        got = ragged_paged_prefill_attention(*a, interpret=interpret)
        sid = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), t // rows)[None]
        return got, oracle(pa.ragged_prefill_attention, *a, sid,
                           prefix_blocks=1)

    def matmul(_quant):
        x, wq, scale = reg.probe_int8_matmul_inputs(*g["matmul"])
        got = int8_matmul(x, wq, scale, out_dtype=jnp.float32,
                          interpret=interpret)
        with jax.default_matmul_precision("highest"):
            ref = x.astype(jnp.float32) @ (
                wq.astype(jnp.float32) * scale[None, :])
        return got, ref

    def sparse(_quant):
        a = reg.probe_mla_sparse_inputs(
            g["batch"], h, 72, 96, n * 32, lens)
        got = pa.sparse_latent_attention(*a, sm_scale=0.2, phase="decode") \
            if not interpret else _sparse_interpret(a)
        return got, oracle(pa.sparse_latent_attention, *a, sm_scale=0.2,
                           phase="decode")

    def _sparse_interpret(a):
        from dynamo_tpu.ops import latent_cache
        from dynamo_tpu.ops.pallas.mla_sparse_attention import (
            mla_sparse_attention,
        )

        q, latent, _, slots, nvalid = a
        q_lo, q_hi = latent_cache.split_query(q)
        return jnp.concatenate(mla_sparse_attention(
            q_lo, q_hi, slots, nvalid,
            latent.reshape(-1, 1, latent.shape[-1]), sm_scale=0.2,
            rows_per_tile=32, interpret=True), axis=-1)

    def masked(_quant):
        from dynamo_tpu.ops.pallas.mla_masked_prefill import (
            mla_sparse_prefill_masked,
        )

        # 27 of 32 tokens over 200 of 384 positions: a dead query tile, a
        # dead key tile, both lengths inside a tile
        q, ctx, bias, lens = reg.probe_mla_masked_inputs(
            32, 384, h, 128, 27, 200)
        got = mla_sparse_prefill_masked(
            q, ctx, bias, lens, heads=h, dv=128, sm_scale=0.2,
            tokens_per_tile=8, keys_per_tile=128, interpret=interpret)
        with jax.default_matmul_precision("highest"):
            sc = jnp.einsum("shd,cd->shc", q.astype(jnp.float32).reshape(
                32, h, 128), ctx.astype(jnp.float32)) * 0.2 + bias[:, None, :]
            ref = jnp.einsum("shc,cd->shd", jax.nn.softmax(sc, axis=-1),
                             ctx.astype(jnp.float32))
        # a dead token's mask is empty: the kernel gives it zeros
        ref = jnp.where(jnp.arange(32)[:, None, None] < 27, ref, 0.0)
        return got, ref.reshape(32 * h, 128)

    def index_scores(_quant):
        from dynamo_tpu.models.glm_dsa import index_scores as xla_form
        from dynamo_tpu.ops.pallas.dsa_index_scores import dsa_index_scores

        # seven rows on three documents of 256 / 512 / 544 keys (a group of
        # three, two of two) and an empty slot, chunks of eight blocks
        q, w, keys, bt, sl = reg.probe_dsa_index_inputs(
            8, 8, 128, 32, 20, [256, 512, 544], [0, 1, 2, 0, -1, 1, 2, 0],
            [40, 7, 33, 1, 0, 70, 64, 90])
        got = dsa_index_scores(q, w, keys, bt, sl, blocks_per_chunk=8,
                               interpret=interpret)
        with jax.default_matmul_precision("highest"):
            ref = xla_form(q[:, None], w[:, None],
                           keys[bt].reshape(8, -1, 128))[:, 0]
        # what a row does not see is whatever the buffers held
        seen = jnp.arange(ref.shape[1])[None, :] < sl[:, None]
        return jnp.where(seen, got, 0.0), jnp.where(seen, ref, 0.0)

    def latent_dma(_quant):
        from dynamo_tpu.ops.pallas.latent_cache_dma import write_rows

        cache, rows, slots = reg.probe_latent_dma_inputs(n * 32, 72, 64)
        ref = np.asarray(cache).copy()
        for r, s_ in zip(np.asarray(rows), np.asarray(slots)):
            if s_ >= 0:
                ref[s_] = r
        # compared as floats below: keep the words exactly representable
        got = write_rows(cache, rows, slots, interpret=interpret)
        return (np.asarray(got) >> 8), (ref >> 8)

    def state_step(_quant):
        from dynamo_tpu.ops.pallas.linear_state import state_update

        a = reg.probe_linear_state_inputs(2, 8, 8, 128)
        ref = reg.linear_state_reference(*a)
        o, new = state_update(*a, interpret=interpret)
        return reg.linear_state_rows(o, new[a[1]]), ref

    def ssm_step(_quant):
        from dynamo_tpu.ops.pallas.ssm_state import state_update

        a = reg.probe_ssm_state_inputs(2, 8, 32, 64, 128, 2)
        ref = reg.ssm_state_reference(*a)
        y, new = state_update(*a, interpret=interpret)
        return reg.linear_state_rows(y, new[a[1]]), ref

    def selective_step(_quant):
        from dynamo_tpu.ops.pallas.selective_state import state_update

        a = reg.probe_selective_step_inputs(2, 8, 16, 8)
        ref = reg.selective_step_reference(*a)
        y, new = state_update(*a, interpret=interpret)
        return reg.linear_state_rows(y, new[a[1]]), ref

    def selective_scan(_quant):
        from dynamo_tpu.ops.pallas.selective_state import state_scan

        a = reg.probe_selective_scan_inputs(2, 8, 16, 16, 64)
        ref = reg.selective_scan_reference(*a)
        y, new = state_scan(*a, interpret=interpret)
        return reg.linear_state_rows(y, new[a[1]][a[2]]), ref

    def experts(_quant):
        from dynamo_tpu.ops.pallas import grouped_matmul as gmm

        # 40 of 192 sorted rows in groups, the last layer of a stack of two
        xs, w, sizes, first = reg.probe_grouped_matmul_inputs(
            192, 2, 8, 256, 384, 40)
        tm = reg.grouped_matmul_row_tile(192, 384)
        plan = gmm.grouped_matmul_plan(sizes, 192, tm)
        got, = gmm.grouped_expert_matmul(xs, (w,), plan, first, tm=tm,
                                         interpret=interpret)
        return got, reg.grouped_matmul_reference(xs, w, sizes, first)

    # tolerances of tests/test_pallas_kernels.py: bf16 operands 3e-2;
    # the int8 matmul rtol 5e-2 / atol 0.5
    cases = {
        "paged_decode_attention_mq": [("decode", decode), ("mq", mq)],
        "paged_prefill_attention": [("prefill", prefill)],
        "ragged_paged_prefill_attention": [("ragged", ragged)],
        "int8_matmul": [("int8_matmul", matmul)],
        "mla_sparse_attention": [("sparse_latent", sparse)],
        "mla_sparse_prefill_masked": [("masked_latent", masked)],
        "dsa_index_scores": [("index_scores", index_scores)],
        "latent_cache_dma": [("latent_write_rows", latent_dma)],
        "linear_state_update": [("state_step", state_step)],
        "ssm_state_update": [("ssm_step", ssm_step)],
        "selective_state_update": [("sel_step", selective_step)],
        "selective_state_scan": [("sel_scan", selective_scan)],
        "grouped_expert_matmul": [("experts", experts)],
    }
    live = [k for k, meta in reg.KERNELS.items() if not meta["placeholder"]]
    assert sorted(live) == sorted(cases), (live, sorted(cases))
    ok = True
    for kernel in live:
        for label, fn in cases[kernel]:
            for quant in ([False] if kernel in (
                    "int8_matmul", "mla_sparse_attention",
                    "mla_sparse_prefill_masked", "dsa_index_scores",
                    "latent_cache_dma",
                    "linear_state_update", "ssm_state_update",
                    "selective_state_update", "selective_state_scan",
                    "grouped_expert_matmul")
                          else [False, True]):
                t0 = time.monotonic()
                got, ref = (np.asarray(x, np.float32) for x in fn(quant))
                rtol, atol = ((5e-2, 0.5) if kernel == "int8_matmul"
                              else (0.0, 3e-2))
                err = float(np.max(np.abs(got - ref)))
                good = bool(np.isfinite(got).all() and np.allclose(
                    got, ref, rtol=rtol, atol=atol))
                ok &= good
                print(f"kernel {label:<12} {'int8-kv' if quant else 'bf16':<8}"
                      f"shape {got.shape} max|err| {err:.2e} "
                      f"(atol {atol}, rtol {rtol}) interpret={interpret} "
                      f"{time.monotonic() - t0:.1f}s "
                      f"{'PASS' if good else 'FAIL'}", flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": ok, "platform": dev.platform}))
    sys.exit(0 if ok else 1)


# ------------------------------------------------------------------ serve ----


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def words(n: int, start: int, vocab: int) -> str:
    """A prompt of exactly n tokens under the word-level tokenizer."""
    return " ".join(f"w{(start + 7 * i) % (vocab - 2)}" for i in range(n))


class Server:
    """One ``python -m dynamo_tpu.cli run in=http out=tpu`` child."""

    def __init__(self, name: str, size: dict, env: dict, tp: int = 1):
        self.name, self.port = name, free_port()
        self.log_path = os.path.join(LOG_DIR, f"{name}.log")
        self.url = f"http://127.0.0.1:{self.port}"
        self.argv = [
            sys.executable, "-m", "dynamo_tpu.cli", "run", "in=http",
            "out=tpu", "--model-path", CKPT_DIR, "--model-name", "smoke",
            "--block-size", str(size["block_size"]),
            "--max-model-len", str(size["max_model_len"]),
            "--max-batch-size", str(size["max_batch_size"]),
            "--prefill-chunk-tokens", str(size["chunk"]),
            "--http-port", str(self.port),
        ] + (["--tp", str(tp)] if tp > 1 else [])
        self._log = open(self.log_path, "w")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            self.argv, cwd=HERE, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)

    def log(self) -> str:
        return open(self.log_path, errors="replace").read()

    async def wait_ready(self, session, deadline: float) -> float:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name}: server exited {self.proc.returncode} "
                    f"before it was ready; end of {self.log_path}:\n"
                    f"{self.log()[-3000:]}")
            try:
                async with session.get(self.url + "/health") as r:
                    if r.status == 200:
                        return time.monotonic() - self.t_spawn
            except OSError:
                pass
            await asyncio.sleep(0.5)
        raise SmokeFailure(f"{self.name}: server not ready in time")

    def startup(self) -> dict:
        """The server's one start-up line (cli.py _log_startup)."""
        m = re.search(r"startup (\{.*\})", self.log())
        if not m:
            raise SmokeFailure(f"{self.name}: no start-up line in the log")
        return json.loads(m.group(1))

    def stop(self) -> None:
        """The server must be alive until now, and gone before the next
        child starts.  cli.py run installs no signal handler, so the
        signal's own exit status is the expected one."""
        alive = self.proc.poll() is None
        if alive:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        if not alive:
            raise SmokeFailure(
                f"{self.name}: server died on its own "
                f"(exit {self.proc.returncode})")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


async def complete(session, server: Server, prompt: str, *, stream=False,
                   logprobs=None, max_tokens=MAX_TOKENS) -> dict:
    """One greedy /v1/completions request, checked: HTTP 200 and exactly
    the requested number of tokens (ignore_eos: random weights may well
    sample the EOS id)."""
    body = {"model": "smoke", "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True, "stream": stream}
    if logprobs:
        body["logprobs"] = logprobs
    t0 = time.monotonic()
    async with session.post(server.url + "/v1/completions", json=body) as r:
        if r.status != 200:
            raise SmokeFailure(
                f"{server.name}: HTTP {r.status}: {(await r.text())[:500]}")
        if not stream:
            out = await r.json()
            out["chunks"] = 1
        else:
            chunks, text, done = 0, "", False
            async for raw in r.content:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    break
                for c in json.loads(data).get("choices", []):
                    if c.get("text"):
                        chunks += 1
                        text += c["text"]
            if not done or chunks < 2:
                raise SmokeFailure(
                    f"{server.name}: SSE stream gave {chunks} chunks, "
                    f"[DONE]={done}")
            # a completions stream carries no usage chunk: count the words
            out = {"choices": [{"text": text}], "chunks": chunks,
                   "usage": {"completion_tokens": len(text.split())}}
    out["seconds"] = time.monotonic() - t0
    n = out["usage"]["completion_tokens"]
    n_words = len(out["choices"][0]["text"].split())
    if n != max_tokens or n_words != max_tokens:
        raise SmokeFailure(
            f"{server.name}: asked for {max_tokens} tokens, usage says {n}, "
            f"text holds {n_words}")
    return out


def metric(text: str, name: str) -> float:
    m = re.search(rf"^{name}(?:\{{[^}}]*\}})? ([0-9.e+-]+)$", text, re.M)
    if not m:
        raise SmokeFailure(f"/metrics has no {name}")
    return float(m.group(1))


async def serve_one_chip(server: Server, size: dict, warm: bool) -> dict:
    import aiohttp

    vocab = size["model"]["vocab_size"]
    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        load_s = await server.wait_ready(s, time.monotonic() + 600)
        # shorter than a block: no prefix is cached, so the repeat takes
        # the same path and a deterministic device gives the same tokens
        short = words(5, 11, vocab)
        first = await complete(s, server, short)
        again = await complete(s, server, short)
        if first["choices"][0]["text"] != again["choices"][0]["text"]:
            raise SmokeFailure(
                f"{server.name}: the same greedy request gave different "
                f"tokens: {first['choices'][0]['text']!r} vs "
                f"{again['choices'][0]['text']!r}")
        served = [again]
        if not warm:
            # several prefill chunks, each after the first over a cached
            # prefix; then two requests at once sharing a long prefix
            served.append(await complete(
                s, server, words(size["long_prompt"], 3, vocab)))
            prefix = words(size["shared_prefix"], 5, vocab)
            served += await asyncio.gather(
                complete(s, server, prefix + " " + words(40, 1000, vocab)),
                complete(s, server, prefix + " " + words(50, 2000, vocab)))
            served.append(await complete(s, server, short, stream=True))
        async with s.get(server.url + "/metrics") as r:
            metrics = await r.text()
    prefills = metric(metrics, "dynamo_tpu_engine_prefill_dispatches_total")
    busy = metric(metrics, "dynamo_tpu_engine_busy_steps_total")
    tokens = metric(metrics, "dynamo_tpu_http_service_output_tokens_total")
    want = MAX_TOKENS * (len(served) + 1)
    if prefills <= 0 or busy - prefills <= 0 or tokens != want:
        raise SmokeFailure(
            f"{server.name}: /metrics says {prefills} prefill dispatches, "
            f"{busy - prefills} decode dispatches, {tokens} output tokens "
            f"(asked for {want})")
    return {
        "load_s": load_s, "first_request_s": first["seconds"],
        "requests": len(served) + 1,
        "serving_s": sum(r["seconds"] for r in served),
        "sse_chunks": served[-1]["chunks"] if not warm else None,
        "prefill_dispatches": prefills, "decode_dispatches": busy - prefills,
    }


def check_log(server: Server, expect_impl: str) -> dict:
    log = server.log()
    if "engine step failed" in log:
        raise SmokeFailure(f"{server.name}: the log holds a failed step")
    up = server.startup()
    for phase, impl in up["attention"].items():
        if not impl.startswith(expect_impl):
            raise SmokeFailure(
                f"{server.name}: {phase} attention is {impl!r}, "
                f"expected {expect_impl}")
    up["cache_hits"] = log.count("Persistent compilation cache hit")
    up["cache_misses"] = log.count("PERSISTENT COMPILATION CACHE MISS")
    return up


def report(server: Server, up: dict, t: dict) -> None:
    note(f"  {server.name}: device {up['platform']} {up['device_kind']!r} "
         f"x{up['device_count']}; native: {up['native']}")
    note(f"  {server.name}: attention {json.dumps(up['attention'])}")
    note(f"  {server.name}: bytes in use after load {up['bytes_in_use']}")
    note(f"  {server.name}: compile cache {up['compile_cache']} — "
         f"{up['cache_hits']} hits, {up['cache_misses']} misses")
    line = f"  {server.name}: set-up {t['load_s']:.1f}s spawn-to-ready"
    if "serving_s" in t:
        line += (
            f" + {t['first_request_s']:.1f}s first request (compiles); "
            f"serving {t['serving_s']:.1f}s for {t['requests'] - 1} later "
            f"requests ({t['prefill_dispatches']:.0f} prefill / "
            f"{t['decode_dispatches']:.0f} decode dispatches)")
    note(line)


def device_of(up: dict) -> dict:
    return {"platform": up["platform"], "kind": up["device_kind"],
            "count": up["device_count"]}


def run_server(name, size, env, fn, expect_impl, tp=1):
    """Start a server, drive ``fn`` against it, stop it, read its log."""
    server = Server(name, size, env, tp=tp)
    try:
        result = asyncio.run(fn(server))
        server.stop()
    finally:
        server.kill()
    up = check_log(server, expect_impl)
    return server, up, result


# ------------------------------------------------------------- four chips ----


async def serve_logprobs(server: Server, size: dict) -> dict:
    """The requests both the --tp 4 and the --tp 1 server answer: greedy,
    with the top-20 logprobs of every position."""
    import aiohttp

    vocab = size["model"]["vocab_size"]
    timeout = aiohttp.ClientTimeout(total=600)
    async with aiohttp.ClientSession(timeout=timeout) as s:
        load_s = await server.wait_ready(s, time.monotonic() + 600)
        prefix = words(size["shared_prefix"], 5, vocab)
        prompts = [words(5, 11, vocab),
                   words(size["long_prompt"], 3, vocab),
                   prefix + " " + words(40, 1000, vocab),
                   prefix + " " + words(50, 2000, vocab)]
        outs = [await complete(s, server, p, logprobs=20, max_tokens=8)
                for p in prompts[:2]]
        outs += await asyncio.gather(*(
            complete(s, server, p, logprobs=20, max_tokens=8)
            for p in prompts[2:]))
    return {"load_s": load_s,
            "top": [o["choices"][0]["logprobs"]["top_logprobs"]
                    for o in outs],
            "tokens": [o["choices"][0]["logprobs"]["tokens"] for o in outs]}


def compare_logprobs(a: dict, b: dict) -> float:
    """Position by position while both runs chose the same token (after
    an argmax flip the contexts differ): the other run's top token is in
    this run's top 20, at least half of the top 20 is shared, and shared
    tokens' logprobs agree within LOGPROB_TOL.  Returns the worst |Δ|."""
    worst = 0.0
    for i, (ta, tb) in enumerate(zip(a["top"], b["top"])):
        for pos, (da, db) in enumerate(zip(ta, tb)):
            both = set(da) & set(db)
            top_a, top_b = max(da, key=da.get), max(db, key=db.get)
            diff = max(abs(da[t] - db[t]) for t in both) if both else 1e9
            worst = max(worst, diff)
            if (top_a not in db or top_b not in da or len(both) < 10
                    or diff > LOGPROB_TOL):
                raise SmokeFailure(
                    f"request {i} position {pos}: tp=4 and tp=1 disagree "
                    f"(top {top_a!r}/{top_b!r}, {len(both)} shared of 20, "
                    f"max |Δlogprob| {diff:.3f} > {LOGPROB_TOL})")
            if a["tokens"][i][pos] != b["tokens"][i][pos]:
                break
    return worst


def four_chips(size: dict, env: dict, expect: str) -> dict:
    """--tp 4, then --tp 1 on the same checkpoint and requests.  Returns
    the device as the --tp 4 server reported it."""
    s4, up4, r4 = run_server(
        "serve-tp4", size, env, lambda s: serve_logprobs(s, size),
        expect, tp=4)
    report(s4, up4, r4)
    if up4["device_count"] != 4:
        raise SmokeFailure(f"--chips 4 found {up4['device_count']} devices")
    # code that has only ever seen one chip may put everything on the
    # first (the CPU reports no memory_stats: nothing to compare there)
    used = up4["bytes_in_use"]
    if None not in used:
        others = sorted(used[1:])
        if used[0] > BALANCE_TOL * others[len(others) // 2]:
            raise SmokeFailure(
                f"device 0 holds {used[0]} bytes after load, the others "
                f"{used[1:]}: the load is not spread over the mesh")
    elif up4["platform"] == "tpu":
        raise SmokeFailure("the TPU reported no memory_stats after load")
    s1, up1, r1 = run_server(
        "serve-tp1", size, env, lambda s: serve_logprobs(s, size), expect)
    report(s1, up1, r1)
    worst = compare_logprobs(r4, r1)
    note(f"  tp=4 vs tp=1: top-20 logprobs agree, worst |Δ| {worst:.4f} "
         f"(tolerance {LOGPROB_TOL})")
    return device_of(up4)


# ------------------------------------------------------------------- main ----


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and probe inputs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the --tp 4 path and its --tp 1 reference")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at tiny widths; proves the script, "
                    "not the chip")
    args = ap.parse_args()
    t_start = time.monotonic()
    size = TINY if args.tiny else REAL
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
    state: dict = {"device": None}
    phases: list[str] = []

    def phase(name: str) -> None:
        if time.monotonic() - t_start > DEADLINE_S:
            raise SmokeFailure(f"out of time before phase {name}")
        phases.append(name)
        note(f"[{time.monotonic() - t_start:6.1f}s] phase {name}")

    failure = None
    try:
        if not os.path.isdir(os.path.join(HERE, "dynamo_tpu")):
            raise SmokeFailure(
                "the dynamo_tpu package is not beside chip_smoke.py")
        os.makedirs(LOG_DIR, exist_ok=True)

        phase("device")
        dev = child("device", "_child_device", {}, env, 300)
        note(f"  jax finds {dev['count']} x {dev['platform']} "
             f"{dev['kind']!r} ({dev['seconds']}s)")
        state["device"] = {k: dev[k] for k in ("platform", "kind", "count")}
        if not args.tiny and (dev["platform"] != "tpu"
                              or dev["count"] != args.chips):
            raise SmokeFailure(
                f"need {args.chips} TPU chip(s), jax finds {dev['count']} x "
                f"{dev['platform']}; --tiny is the CPU rehearsal")

        phase("checkpoint")
        ck = child("checkpoint", "_child_checkpoint",
                   {"model": size["model"], "dst": CKPT_DIR,
                    "seed": args.seed}, env, 600)
        note(f"  {ck['params']:,} parameters, {ck['bytes']:,} bytes of bf16 "
             f"safetensors in {ck['seconds']}s (set-up)")

        # the attention implementation every phase must report
        expect = "xla" if args.tiny else "pallas"
        if args.chips == 4:
            phase("serve-tp4+tp1")
            state["device"] = four_chips(size, env, expect)
        else:
            phase("serve")
            server, up, t = run_server(
                "serve", size, env,
                lambda s: serve_one_chip(s, size, warm=False), expect)
            state["device"] = device_of(up)
            report(server, up, t)
            if time.monotonic() - t_start < DEADLINE_S / 2:
                phase("serve-warm")
                server, up, t = run_server(
                    "serve-warm", size, env,
                    lambda s: serve_one_chip(s, size, warm=True), expect)
                report(server, up, t)
                if up["cache_hits"] == 0:
                    raise SmokeFailure(
                        "second server start hit the compile cache 0 times")
            phase("kernels")
            k = child("kernels", "_child_kernels",
                      {"kernel": size["kernel"], "interpret": args.tiny,
                       "seed": args.seed}, env, 900)
            note(f"  all kernels match their XLA oracle on {k['platform']} "
                 f"({k['seconds']}s)")
        if not args.tiny and state["device"]["platform"] != "tpu":
            raise SmokeFailure(f"the server ran on {state['device']}")
    except Exception as e:  # the boundary: every failure gets its last line
        if not isinstance(e, SmokeFailure):
            traceback.print_exc(file=sys.stdout)
        failure = f"{type(e).__name__}: {e}"
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)

    note(f"[{time.monotonic() - t_start:6.1f}s] phases run: "
         f"{' '.join(phases)}")
    assert "jax" not in sys.modules, "the parent must stay off jax"
    if failure:
        note(f"FAILED in phase {phases[-1] if phases else 'start'}: {failure}")
        print(json.dumps({"ok": False, "device": state["device"],
                          "failed": phases[-1] if phases else "start"}))
        return 1
    print(json.dumps({"ok": True, "device": state["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
