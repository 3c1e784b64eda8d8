"""Standalone Pallas kernel probes on the real backend.

Compiles each kernel variant (bf16 / int8-KV x decode / prefill / mq) at
a representative serving geometry and prints PASS/FAIL with the full
Mosaic error — the fast iteration loop for kernel lowering issues that
interpret-mode tests cannot catch (round 4 found two: partial-tile scale
DMA slices, and the prefill kernel's sublane-indexed q/out slices).

Probe INPUTS come from ``ops/pallas/registry.py``'s ``probe_*_inputs``
builders — the same tensors bench.py's pre-run probes and the kernel
plane's interpret audits consume — so a kernel this sweep exercises is
by construction one the registry knows (``dynamo-tpu lint --kern``'s
KN006 census flags any registered kernel that loses probe coverage).

Usage:  python benchmarks/probe_kernels.py [bf16|int8|all] [8b|1b|probe]
"""

from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GEOMS = {
    # h, hk, d, batch, max_len, bs, s_prefill
    "probe": dict(h=8, hk=4, d=64, batch=1, max_len=160, bs=16, s=128),
    "1b": dict(h=32, hk=8, d=64, batch=64, max_len=2048, bs=32, s=512),
    "8b": dict(h=32, hk=8, d=128, batch=64, max_len=1024, bs=32, s=512),
}



def time_topk() -> None:
    """Time the three top-k paths at serving shape [64, 128256] — decides
    whether the dual approx/exact sampler design can collapse to
    always-exact (run: probe_kernels.py topk)."""
    import time

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.sampling import _exact_top_k_tiled

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128256), jnp.float32)
    jax.block_until_ready(x)
    paths = {
        "approx_max_k": jax.jit(lambda a: jax.lax.approx_max_k(
            a, 64, recall_target=0.95)),
        "exact_tiled": jax.jit(lambda a: _exact_top_k_tiled(a, 64)),
        "lax_top_k": jax.jit(lambda a: jax.lax.top_k(a, 64)),
    }
    for name, fn in paths.items():
        jax.block_until_ready(fn(x))  # compile
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(x)
        jax.block_until_ready(out)
        print(f"topk/{name}: {(time.perf_counter() - t0) / 20 * 1e3:.3f} ms")


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which == "topk":
        time_topk()
        return
    geom = GEOMS[sys.argv[2] if len(sys.argv) > 2 else "8b"]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_mq,
    )
    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention, ragged_paged_prefill_attention,
    )
    from dynamo_tpu.ops.pallas.registry import (
        probe_decode_inputs, probe_int8_matmul_inputs, probe_prefill_inputs,
        probe_ragged_inputs,
    )

    h, hk, d, batch, max_len, bs, s = (
        geom["h"], geom["hk"], geom["d"], geom["batch"], geom["max_len"],
        geom["bs"], geom["s"])
    m = -(-max_len // bs)
    n = min(batch * m + 4, 4096)
    lens = np.full((batch,), min(4 * bs, max_len), np.int32)

    def probe(label, fn):
        try:
            out = fn()
            jax.block_until_ready(out)
            print(f"PASS {label}")
            return True
        except Exception as e:
            msg = str(e)
            print(f"FAIL {label}: {type(e).__name__}")
            print("\n".join(msg.splitlines()[:30]))
            if os.environ.get("DYNAMO_PROBE_TRACE"):
                traceback.print_exc()
            return False

    def unified_inputs(quant: bool):
        # unified mixed dispatch: a DECODE row (1 fresh token, start NOT
        # block-aligned — the full-cached-prefix DMA path) ahead of a
        # block-aligned prefill span on the same flat axis; the builder
        # supplies tensors, only the row layout is overridden here
        args = list(probe_ragged_inputs(bs + s, 2, h, hk, d, bs, n, m,
                                        quant=quant))
        args[6:9] = [jnp.asarray([2 * bs + 3 + 1, s], jnp.int32),  # seq_lens
                     jnp.asarray([2 * bs + 3, 0], jnp.int32),      # starts
                     jnp.asarray([0, bs], jnp.int32)]              # roff
        return args

    variants = []
    for mode in (["bf16", "int8"] if which == "all" else [which]):
        q8 = mode == "int8"
        variants += [
            (f"decode/{mode}", lambda q8=q8: paged_decode_attention(
                *probe_decode_inputs(batch, h, hk, d, bs, n, m, lens,
                                     quant=q8))),
            (f"mq/{mode}", lambda q8=q8: paged_decode_attention_mq(
                *probe_decode_inputs(batch, h, hk, d, bs, n, m, lens,
                                     quant=q8, s_q=4))),
            (f"prefill/{mode}", lambda q8=q8: paged_prefill_attention(
                *probe_prefill_inputs(1, s, h, hk, d, bs, n, m, quant=q8))),
            # token-budget ragged prefill: two rows packed on one flat
            # axis, each with a cached prefix (per-row DMA path)
            (f"ragged/{mode}", lambda q8=q8: ragged_paged_prefill_attention(
                *probe_ragged_inputs(s, 2, h, hk, d, bs, n, m, quant=q8))),
            (f"unified/{mode}", lambda q8=q8: ragged_paged_prefill_attention(
                *unified_inputs(q8))),
        ]
    # dequant-in-kernel int8 matmul at decode and prefill row counts
    from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul

    wk, wn = hk * d * (h // hk), 14336  # 8B-ish ffn width
    for rows in (64, 512):
        variants.append((
            f"int8_matmul/m{rows}",
            lambda rows=rows: int8_matmul(
                *probe_int8_matmul_inputs(rows, wk, wn),
                out_dtype=jnp.bfloat16),
        ))
    # grouped-MoE ragged_dot lowering (Mixtral-ish shapes: E=8 experts,
    # 512 routed token-slots, H=4096, F=14336/4 keeps the probe light)
    def moe_ragged():
        e, t, hd_, f = 8, 512, hk * d * (h // hk), 3584
        xs = jnp.ones((t, hd_), jnp.bfloat16)
        w = jnp.ones((e, hd_, f), jnp.bfloat16)
        sizes = jnp.full((e,), t // e, jnp.int32)
        return jax.lax.ragged_dot(xs, w, sizes)

    variants.append(("moe/ragged_dot", moe_ragged))
    # sparse latent attention at GLM-5.2's widths (64 heads, rows of 576)
    # and the DMA movers of its cache; the dispatch picks the kernel
    from dynamo_tpu.ops.pallas.latent_cache_dma import write_rows
    from dynamo_tpu.ops.pallas.registry import (
        probe_latent_dma_inputs, probe_mla_sparse_inputs,
    )
    from dynamo_tpu.ops.paged_attention import sparse_latent_attention

    for phase, nq in (("decode", batch), ("prefill", 256)):
        variants.append((
            f"mla_sparse/{phase}",
            lambda phase=phase, nq=nq: sparse_latent_attention(
                *probe_mla_sparse_inputs(
                    nq, 64, 576, 2048, 1 << 16,
                    np.linspace(1, 4096, nq).astype(np.int32)),
                sm_scale=1 / 16, phase=phase)))
    from dynamo_tpu.ops.pallas.mla_masked_prefill import mla_masked_prefill
    from dynamo_tpu.ops.pallas.registry import probe_mla_masked_inputs

    variants.append((
        "mla_masked/prefill",
        lambda: mla_masked_prefill(
            *probe_mla_masked_inputs(512, 4096, 64, 640), heads=64, dv=512,
            sm_scale=1 / 16)))
    variants.append((
        "latent_cache/write_rows",
        lambda: write_rows(*probe_latent_dma_inputs(1 << 16, 576, 2048))))
    ok = all([probe(lbl, fn) for lbl, fn in variants])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
