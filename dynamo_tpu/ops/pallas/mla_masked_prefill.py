"""Latent attention of a long prefill chunk over a selection, as a mask.

A chunk of S query tokens, each with its own selection of at most
``index_topk`` of the C positions it can see.  Gathering each query's rows
(ops/pallas/mla_sparse_attention.py) would fetch S x index_topk rows a layer;
here every key of the context is scored once for all the chunk's queries on
the matrix unit, and what was not selected is masked.  The same numbers, by
flash attention with one shared key/value head:

  q     [S·H, Dq]  bf16   latent-space queries, the H heads of a token
                          adjacent (row t·H + h), zero padded to Dq
  ctx   [C, Dq]    bf16   the context's unpacked cache rows
                          (latent_cache.context_rows), cut to Dq; the first
                          Dv elements of a row are what is summed
  bias  [S, C]     f32    0 where query t attends to position c, -1e30
                          elsewhere (selection and causality)

Grid (S / tq, C / tk): a tile of tq tokens (tq·H rows) meets a tile of tk
keys; running maximum, sum and accumulator live in VMEM across the key tiles
of a query tile.  Returns f32 [S·H, Dv].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import (
    MLA_MASKED_KEYS_PER_TILE,
    MLA_MASKED_TOKENS_PER_TILE,
)

__all__ = ["mla_masked_prefill"]

NEG_INF = -1e30


def _kernel(q_ref, ctx_ref, bias_ref, out_ref, m_ref, l_ref, acc_ref, *,
            heads: int, dv: int, sm_scale: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[...]                                  # [tq·H, Dq]
    keys = ctx_ref[...]                             # [tk, Dq]
    tq, tk = bias_ref.shape
    s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    bias = jnp.broadcast_to(bias_ref[...][:, None, :], (tq, heads, tk))
    ok = bias.reshape(tq * heads, tk) > 0.5 * NEG_INF
    # chosen, not added: whatever a masked key's score is, it is gone
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a row with nothing selected so far keeps m = -1e30: exp(s - m) would
    # be exp(0) = 1 for its masked keys, so weigh by the mask explicitly
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    l_ref[...] = jnp.broadcast_to(
        l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(keys.dtype), keys[:, :dv],
        preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-9)


@functools.partial(
    jax.jit, static_argnames=("heads", "dv", "sm_scale", "tokens_per_tile",
                              "keys_per_tile", "interpret"))
def mla_masked_prefill(
    q: jax.Array, ctx: jax.Array, bias: jax.Array, *, heads: int, dv: int,
    sm_scale: float,
    tokens_per_tile: int = MLA_MASKED_TOKENS_PER_TILE,
    keys_per_tile: int = MLA_MASKED_KEYS_PER_TILE,
    interpret: bool = False,
) -> jax.Array:
    s, c = bias.shape
    dq = q.shape[1]
    tq = min(tokens_per_tile, s)
    tk = min(keys_per_tile, c)
    if s % tq or c % tk or q.shape[0] != s * heads or ctx.shape != (c, dq):
        raise ValueError(
            f"shapes q {q.shape} ctx {ctx.shape} bias {bias.shape} do not "
            f"tile by ({tq}, {tk}) with {heads} heads")
    rows = tq * heads
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, dv=dv, sm_scale=sm_scale),
        grid=(s // tq, c // tk),
        in_specs=[
            pl.BlockSpec((rows, dq), lambda i, j: (i, 0)),
            pl.BlockSpec((tk, dq), lambda i, j: (j, 0)),
            pl.BlockSpec((tq, tk), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rows, dv), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s * heads, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_masked_prefill",
    )(q.astype(jnp.bfloat16), ctx.astype(jnp.bfloat16), bias)
