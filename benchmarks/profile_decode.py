"""Decode-step breakdown profiler (VERDICT r2 ask #9).

Times the components of one decode step in isolation — weight streaming
(the bf16/int8 matmul chain with attention stubbed), the paged-attention
kernel, logits+sampling, and the whole decode step — so the gap
between measured ITL and the HBM roofline is attributable, not guessed.

Run on the real chip:  python benchmarks/profile_decode.py [1b|8b]
Env: DYNAMO_PROF_BATCH (64), DYNAMO_PROF_CTX (512), DYNAMO_PROF_QUANT
(int8|none), DYNAMO_PROF_PARTS (comma list of exact part names to run a
subset).

Prints a JSON line per component: {"part", "ms", "hbm_gb", "gbps"}.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODELS = {
    "tiny": dict(vocab_size=2048, hidden_size=256, intermediate_size=512,
                 num_layers=4, num_heads=8, num_kv_heads=4,
                 max_position_embeddings=2048, rope_theta=500000.0),
    "1b": dict(vocab_size=128256, hidden_size=2048, intermediate_size=8192,
               num_layers=16, num_heads=32, num_kv_heads=8, head_dim=64,
               max_position_embeddings=8192, rope_theta=500000.0,
               tie_word_embeddings=True),
    "8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_layers=32, num_heads=32, num_kv_heads=8,
               max_position_embeddings=8192, rope_theta=500000.0),
    # Mixtral-8x7B architecture scaled to fit one chip at int8 (half the
    # layers): for A/B-ing grouped ragged_dot dispatch vs the dense
    # oracle (DYNAMO_MOE_DENSE=1) on the same weights
    "moe": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                num_layers=16, num_heads=32, num_kv_heads=8,
                num_experts=8, num_experts_per_tok=2,
                max_position_embeddings=8192, rope_theta=1000000.0),
}


def timeit(fn, *args, iters=20, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main() -> None:
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # jax may already be imported: pin the platform through
        # jax.config as well as the env var
        from dynamo_tpu.utils import force_cpu_devices

        force_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()  # warm-start respawns (VERDICT r5 next #1)
    from dynamo_tpu.engine.core import multi_decode_step
    from dynamo_tpu.engine.sampling import sample_full
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.llama import LlamaModel
    from dynamo_tpu.ops.pallas.decode_attention import paged_decode_attention

    name = sys.argv[1] if len(sys.argv) > 1 else "8b"
    on_accel = jax.default_backend() != "cpu"
    batch = int(os.environ.get("DYNAMO_PROF_BATCH", "64" if on_accel else "8"))
    ctx = int(os.environ.get("DYNAMO_PROF_CTX", "512" if on_accel else "64"))
    quant = os.environ.get("DYNAMO_PROF_QUANT", "int8" if on_accel else "none")
    bs = 32 if on_accel else 16
    if not on_accel:
        name = "tiny"

    cfg = ModelConfig(**MODELS[name],
                      dtype="bfloat16" if on_accel else "float32")
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), quantized=quant == "int8")
    num_blocks = batch * (ctx // bs) + 8
    cache = model.init_kv_cache(num_blocks, bs)
    jax.block_until_ready(params)

    wbytes = 1 if quant == "int8" else 2
    h, inter, v_, nl = (cfg.hidden_size, cfg.intermediate_size,
                        cfg.vocab_size, cfg.num_layers)
    hd = cfg.head_dim
    # MoE: every expert's gate/up/down streams each decode step (all
    # routed experts at batch >= E/k in practice; count all E — the
    # bandwidth question the moe config A/Bs is weight-read-bound)
    mlp_w = 3 * h * inter * (cfg.num_experts if cfg.is_moe else 1)
    router_w = h * cfg.num_experts if cfg.is_moe else 0
    param_gb = (nl * (h * cfg.num_heads * hd + 2 * h * cfg.num_kv_heads * hd
                      + cfg.num_heads * hd * h + mlp_w + router_w)
                + v_ * h * (1 if cfg.tie_word_embeddings else 2)) * wbytes / 1e9
    kv_gb = (batch * ctx * 2 * cfg.num_kv_heads * hd * nl * 2) / 1e9

    tokens = jnp.ones((batch,), jnp.int32)
    positions = jnp.full((batch,), ctx - 1, jnp.int32)
    m = ctx // bs
    bt = (jnp.arange(batch)[:, None] * m + jnp.arange(m)[None, :]).astype(jnp.int32) % num_blocks
    seq_lens = jnp.full((batch,), ctx, jnp.int32)
    limits = jnp.full((batch,), ctx + 1, jnp.int32)
    rng = jax.random.PRNGKey(1)
    temp = jnp.zeros((batch,), jnp.float32)
    topk = jnp.zeros((batch,), jnp.int32)
    topp = jnp.ones((batch,), jnp.float32)

    def emit(part, ms, gb):
        print(json.dumps({
            "part": part, "ms": round(ms, 3), "hbm_gb": round(gb, 3),
            "gbps": round(gb / (ms / 1e3), 1) if ms else None,
        }))

    parts_env = os.environ.get("DYNAMO_PROF_PARTS", "")
    sel = {w.strip() for w in parts_env.split(",") if w.strip()}

    def want(name: str) -> bool:
        # exact part names — sweeps re-measure only the env-sensitive
        # components (substring matching would catch e.g. "attention"
        # inside "forward_no_attention")
        return not sel or name in sel

    # 1. weights-only: forward with attention output zeroed via 0-len ctx
    if want("forward_no_attention"):
        zero_lens = jnp.zeros((batch,), jnp.int32)
        fwd = jax.jit(lambda p, c, t: model.forward(
            p, t[:, None], jnp.zeros((batch, 1), jnp.int32), c, bt, zero_lens,
            jnp.full((batch, 1), -1, jnp.int32))[0])
        ms = timeit(lambda: fwd(params, cache, tokens))
        emit("forward_no_attention", ms, param_gb - v_ * h * wbytes / 1e9)

    # 2. paged attention kernel alone (per layer x layers), at the tiling
    # the serving path takes (registry.decode_tiling)
    if want("attention_all_layers"):
        q = jnp.ones((batch, cfg.num_heads, hd), cfg.jax_dtype)
        att = jax.jit(lambda qq, cc: paged_decode_attention(
            qq, cc, jnp.int32(0), bt, seq_lens, interpret=not on_accel))
        ms_layer = timeit(lambda: att(q, cache))
        emit("attention_all_layers", ms_layer * nl, kv_gb)

    # 3. logits + sampling
    if want("logits_sampling"):
        hidden = jnp.ones((batch, h), cfg.jax_dtype)
        lg = jax.jit(lambda p, hh: sample_full(
            model.compute_logits(p, hh), rng, temp, topk, topp))
        ms = timeit(lambda: lg(params, hidden))
        emit("logits_sampling", ms, v_ * h * wbytes / 1e9)

    # 4. the whole decode step (what the engine dispatches).  No donation
    # here: the profiler reuses the same cache buffer across timed calls
    if want("single_step_dispatch"):
        one = jax.jit(functools.partial(
            multi_decode_step, model, block_size=bs,
        ))
        ms1 = timeit(
            lambda: one(params, cache, tokens, positions, bt, seq_lens,
                        limits, rng, temp, topk, topp)[0],
            iters=10, warmup=2,
        )
        emit("single_step_dispatch", ms1, param_gb + kv_gb)


if __name__ == "__main__":
    main()
