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
  lens  [2]        int32  how much of the two padded shapes exists: the
                          chunk's live tokens (the first ``lens[0]`` of S)
                          and the context's length (the first ``lens[1]`` of
                          C); prefetched scalars

Grid (S / tq, C / tk): a tile of tq tokens (tq·H rows) meets a tile of tk
keys; running maximum, sum and accumulator live in VMEM across the key tiles
of a query tile.  A step whose query tile holds no live token, or whose key
tile lies wholly past the context, computes nothing, and its blocks are the
last live ones, which are in VMEM already: a question of 160 tokens over
24.7 k rows in a (256, 33,280) shape runs 10 x 49 of the 16 x 65 steps.
Returns f32 [S·H, Dv]; the rows of a dead query tile are zeros.

The call is named for the operation, as the gather's two are
(ops/pallas/mla_sparse_attention.py): ``mla_sparse_prefill_masked`` in a
profile, read by cellbench's ``kernel.prefill_attn_roofline`` like
``mla_sparse_prefill``.
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

__all__ = ["mla_sparse_prefill_masked", "KERNEL_NAME"]

KERNEL_NAME = "mla_sparse_prefill_masked"
NEG_INF = -1e30


def _last_live(lens, tq: int, tk: int):
    """Indices of the last query tile with a live token and of the last key
    tile that reaches into the context (0 where there is none)."""
    return (jnp.maximum((lens[0] + tq - 1) // tq, 1) - 1,
            jnp.maximum((lens[1] + tk - 1) // tk, 1) - 1)


def _kernel(lens_ref, q_ref, ctx_ref, bias_ref, out_ref, m_ref, l_ref,
            acc_ref, *, heads: int, dv: int, sm_scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    tq, tk = bias_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when((i * tq < lens_ref[0]) & (j * tk < lens_ref[1]))
    def _tile():
        q = q_ref[...]                                  # [tq·H, Dq]
        keys = ctx_ref[...]                             # [tk, Dq]
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        bias = jnp.broadcast_to(bias_ref[...][:, None, :], (tq, heads, tk))
        ok = bias.reshape(tq * heads, tk) > 0.5 * NEG_INF
        # chosen, not added: whatever a masked key's score is, it is gone
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with nothing selected so far keeps m = -1e30: exp(s - m)
        # would be exp(0) = 1 for its masked keys, so weigh by the mask
        # explicitly
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(keys.dtype), keys[:, :dv],
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        # a dead query tile kept its zeros: 0 / 1e-9
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-9)


@functools.partial(
    jax.jit, static_argnames=("heads", "dv", "sm_scale", "tokens_per_tile",
                              "keys_per_tile", "interpret"))
def mla_sparse_prefill_masked(
    q: jax.Array, ctx: jax.Array, bias: jax.Array, lens: jax.Array, *,
    heads: int, dv: int, sm_scale: float,
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

    def q_tile(i, j, lens):
        return jnp.minimum(i, _last_live(lens, tq, tk)[0])

    def key_tile(i, j, lens):
        # a dead step names the block the last live one held: nothing moves
        qi, kj = _last_live(lens, tq, tk)
        return jnp.where(i <= qi, jnp.minimum(j, kj), kj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s // tq, c // tk),
        in_specs=[
            pl.BlockSpec((rows, dq), lambda *a: (q_tile(*a), 0)),
            pl.BlockSpec((tk, dq), lambda *a: (key_tile(*a), 0)),
            pl.BlockSpec((tq, tk), lambda *a: (q_tile(*a), key_tile(*a))),
        ],
        out_specs=pl.BlockSpec((rows, dv), lambda i, j, lens: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, dv=dv, sm_scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s * heads, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(lens.astype(jnp.int32), q.astype(jnp.bfloat16),
      ctx.astype(jnp.bfloat16), bias)
