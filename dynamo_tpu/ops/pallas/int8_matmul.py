"""Dequant-in-kernel int8 matmul — the standby fix for a decode-ITL
hypothesis (ROADMAP S3): if the profiler shows XLA materializing
bf16-converted weight tiles to HBM (instead of fusing the convert into
the matmul operand load), int8 weight-only serving loses its entire
bandwidth win. This kernel guarantees the int8->bf16 convert happens in
VMEM: weight tiles stream from HBM as int8, convert on-chip, hit the MXU,
and the per-output-channel scale applies in the epilogue.

Gated OFF by default (DYNAMO_PALLAS_INT8_MATMUL=1 enables it in
models/quant.py's matmul) so it can be A/B-measured against the XLA path
the moment hardware answers; oracle parity is pinned in
tests/test_pallas_kernels.py either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import (
    INT8_MATMUL_BK,
    INT8_MATMUL_BM,
    INT8_MATMUL_BN,
)

__all__ = ["int8_matmul", "BM", "BN", "BK"]

# default block sizes — owned by the kernel registry (the audit prices
# against the same table); re-exported so the routing precheck in
# models/quant.py and the kernel's tiling asserts can never disagree
BM, BN, BK = INT8_MATMUL_BM, INT8_MATMUL_BN, INT8_MATMUL_BK


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 tile -> bf16 in VMEM -> MXU; HBM only ever saw int8 bytes
    acc_ref[:] += jax.lax.dot(
        x_ref[:].astype(jnp.bfloat16),
        w_ref[:].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[:] = (
            acc_ref[:] * s_ref[0, :].astype(jnp.float32)[None, :]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "bm", "bn", "bk", "interpret"),
)
def int8_matmul(
    x: jax.Array,       # [M, K] bf16/f32
    wq: jax.Array,      # [K, N] int8
    scale: jax.Array,   # [N] f32 — per-output-channel
    out_dtype=None,
    bm: int = BM,
    bn: int = BN,
    bk: int = BK,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(wq, scale)`` with the convert inside the kernel.

    Grid (M/bm, N/bn, K/bk); the K axis is the sequential reduction (TPU
    grids execute in order), accumulating into VMEM scratch and applying
    the scale at the last K step.  Dims must tile exactly — model dims
    are 128-multiples, and callers fall back to the XLA path otherwise.
    """
    m, k = x.shape
    k2, n = wq.shape
    assert k == k2, (x.shape, wq.shape)
    out_dtype = out_dtype or x.dtype
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            # scale rides as [1, N] on the standard f32 (8,128) layout —
            # a 1-D f32 operand's XLA layout is T(1024)-tiled, which
            # Mosaic rejects for 512-wide blocks on real TPUs
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="int8_matmul",
    )(x, wq, scale.reshape(1, n))
