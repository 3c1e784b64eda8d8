"""The two-part paged cache of a latent-attention model with a sparse-attention
indexer (models/glm_dsa.py), and the XLA forms of attention over it.

``latent``   uint32 [L, N, Bs, 1, W]: one row a token and layer, held once.
             A row is the token's latent (c_kv ‖ roped k_pe, ``width``
             bf16 elements) padded to 2·W elements; word w packs element w
             in its low half and element W + w in its high half.  W is a
             multiple of 128, so a row is whole 512-byte lane groups, and
             the unit second-minor axis gives the array a (1, 128) tiling
             on the TPU: a row is contiguous, padded to nothing, and one
             row is a legal DMA (ops/pallas/mla_sparse_attention.py).
             GLM-5.2: width 576 -> W 384, 1,536 B a token and layer.
``index_k``  bf16 [Lf, N, Bs, Di]: the indexer's key of a token, in the
             layers that compute an index (``Lf`` of the ``L``).

Both index blocks on axis 1 and share the engine's block table, so a block
id is one block of every layer in both parts, and prefix reuse carries both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "latent_words", "init_latent_cache", "pack_rows", "unpack_rows",
    "split_query", "write_rows", "write_latent", "context_rows",
    "sparse_attention_xla", "dense_masked_attention",
    "masked_attention",
    "kernels_on",
]

LANES = 128
NEG_INF = -1e30


def latent_words(width: int) -> int:
    """Words of a cache row that holds ``width`` bf16 elements."""
    return LANES * math.ceil(width / (2 * LANES))


def init_latent_cache(num_layers: int, index_layers: int, num_blocks: int,
                      block_size: int, width: int, index_dim: int, dtype):
    return {
        "latent": jnp.zeros(
            (num_layers, num_blocks, block_size, 1, latent_words(width)),
            jnp.uint32),
        "index_k": jnp.zeros(
            (index_layers, num_blocks, block_size, index_dim), dtype),
    }


def _pad_to(x: jax.Array, n: int) -> jax.Array:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])


def pack_rows(rows: jax.Array) -> jax.Array:
    """[..., width] -> uint32 [..., W]."""
    w = latent_words(rows.shape[-1])
    bits = jax.lax.bitcast_convert_type(
        _pad_to(rows.astype(jnp.bfloat16), 2 * w), jnp.uint16
    ).astype(jnp.uint32)
    return bits[..., :w] | (bits[..., w:] << 16)


def unpack_rows(words: jax.Array) -> jax.Array:
    """uint32 [..., W] -> bf16 [..., 2·W] (the row, zero padded)."""
    lo = (words & jnp.uint32(0xFFFF)).astype(jnp.uint16)
    hi = (words >> 16).astype(jnp.uint16)
    return jax.lax.bitcast_convert_type(
        jnp.concatenate([lo, hi], axis=-1), jnp.bfloat16)


def split_query(q: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., width] -> the query on the low and on the high halves of a
    row's words, [..., W] each."""
    w = latent_words(q.shape[-1])
    q = _pad_to(q, 2 * w)
    return q[..., :w], q[..., w:]


def kernels_on() -> bool:
    """Whether the latent part is touched by DMA kernels
    (ops/pallas/latent_cache_dma.py) or by XLA's scatter and gather: the
    rule of ``sparse_attention_impl``, made before tracing."""
    from dynamo_tpu.ops.paged_attention import sparse_attention_impl

    return sparse_attention_impl("prefill")[0] == "pallas"


def write_latent(latent: jax.Array, layer, words: jax.Array,
                 slots: jax.Array) -> jax.Array:
    """Write packed rows ``words`` [T, W] of one layer at flat token slots
    [T] (a negative slot writes nothing)."""
    if not kernels_on():
        return write_rows(latent, layer, words, slots)
    from dynamo_tpu.ops.pallas.latent_cache_dma import write_rows as dma

    l, n, bs, _, w = latent.shape
    slots = jnp.where(slots < 0, -1, slots + layer * (n * bs))
    return dma(latent.reshape(l * n * bs, 1, w), words[:, None, :],
               slots).reshape(latent.shape)


def context_rows(latent: jax.Array, layer, block_tables: jax.Array
                 ) -> jax.Array:
    """The rows of each sequence's blocks, unpacked: block_tables [B, Mc]
    -> bf16 [B, Mc·Bs, 2·W]."""
    l, n, bs, _, w = latent.shape
    b, mc = block_tables.shape
    if kernels_on():
        from dynamo_tpu.ops.pallas.latent_cache_dma import gather_blocks

        words = gather_blocks(latent.reshape(l * n, bs, 1, w),
                              (block_tables + layer * n).reshape(b * mc))
    else:
        words = latent[layer, block_tables]
    return unpack_rows(words.reshape(b, mc * bs, w))


def write_rows(part: jax.Array, layer, rows: jax.Array, slots: jax.Array):
    """Write ``rows`` [T, ...] of one layer at flat token slots [T]
    (block·Bs + offset; a negative slot writes nothing)."""
    l, n, bs = part.shape[:3]
    flat = part.reshape(l, n * bs, *part.shape[3:])
    slots = jnp.where(slots < 0, n * bs, slots)
    flat = flat.at[layer, slots].set(
        rows.reshape(rows.shape[0], *part.shape[3:]).astype(part.dtype),
        mode="drop")
    return flat.reshape(part.shape)


def sparse_attention_xla(q: jax.Array, latent: jax.Array, layer,
                         slots: jax.Array, nvalid: jax.Array,
                         sm_scale: float) -> jax.Array:
    """The oracle of the sparse kernel: q [N, H, width], slots [N, K] flat
    token slots of ``layer``, nvalid [N].  Returns f32 [N, H, 2·W]: the
    softmax-weighted sum of each query's rows."""
    l, n, bs, _, w = latent.shape
    rows = unpack_rows(latent.reshape(l, n * bs, w)[layer, slots])  # [N,K,2W]
    q = _pad_to(q, 2 * w).astype(jnp.bfloat16)
    s = jnp.einsum("nhd,nkd->nhk", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    ok = jnp.arange(slots.shape[1])[None, :] < nvalid[:, None]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jnp.where(ok[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("nhk,nkd->nhd", p.astype(jnp.bfloat16), rows,
                      preferred_element_type=jnp.float32)


def masked_attention(q: jax.Array, latent: jax.Array, layer,
                     block_tables: jax.Array, mask: jax.Array,
                     sm_scale: float, dv: int) -> jax.Array:
    """Attention of q [B, S, H, width] over the first C positions of each
    sequence's block table, restricted to ``mask`` [B, S, C]: f32
    [B, S, H, dv'] whose first ``dv`` elements are the weighted sum of the
    rows' first ``dv``.  On the TPU, one sequence at a time, the kernel of
    ops/pallas/mla_masked_prefill.py; else ``dense_masked_attention``."""
    context = context_rows(latent, layer, block_tables)
    b, s, h, width = q.shape
    c = mask.shape[-1]
    dq, dvp = -(-width // LANES) * LANES, -(-dv // LANES) * LANES
    tk = max((d for d in range(LANES, 513, LANES) if c % d == 0), default=0)
    if not (kernels_on() and b == 1 and tk and s % 8 == 0):
        return dense_masked_attention(q, context, mask, sm_scale)
    from dynamo_tpu.ops.pallas.mla_masked_prefill import mla_masked_prefill
    from dynamo_tpu.ops.pallas.registry import MLA_MASKED_TOKENS_PER_TILE

    tq = max(d for d in (MLA_MASKED_TOKENS_PER_TILE, 8) if s % d == 0)
    out = mla_masked_prefill(
        _pad_to(q[0], dq).reshape(s * h, dq), context[0, :, :dq],
        jnp.where(mask[0], 0.0, NEG_INF).astype(jnp.float32),
        heads=h, dv=dvp, sm_scale=sm_scale, tokens_per_tile=tq,
        keys_per_tile=tk)
    return out.reshape(1, s, h, dvp)


def dense_masked_attention(q: jax.Array, context: jax.Array,
                           mask: jax.Array, sm_scale: float,
                           tile_tokens: int = 256) -> jax.Array:
    """Attention of q [B, S, H, width] over ``context`` [B, C, 2·W] (the
    unpacked rows of each sequence's first C tokens, ``context_rows``),
    restricted to ``mask`` [B, S, C] (the selected and causal positions).
    The context is read a tile at a time with a running softmax, so no
    [S, H, C] array exists.  Returns f32 [B, S, H, 2·W].  This is how a long
    prefill chunk attends: every key is scored once for all the chunk's
    queries on the matrix unit, where a gather would fetch each query's rows
    separately."""
    b, s, h, _ = q.shape
    c, w2 = context.shape[1:]
    w = w2 // 2
    tk = max(d for d in range(1, min(tile_tokens, c) + 1) if c % d == 0)
    q = _pad_to(q, 2 * w).astype(jnp.bfloat16)

    def tile(carry, t):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(context, t * tk, tk, axis=1)
        ok = jax.lax.dynamic_slice_in_dim(mask, t * tk, tk, axis=2)
        sc = jnp.einsum("bshd,bkd->bshk", q, rows,
                        preferred_element_type=jnp.float32) * sm_scale
        sc = jnp.where(ok[:, :, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok[:, :, None, :], jnp.exp(sc - m_new), 0.0)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bshk,bkd->bshd", p.astype(jnp.bfloat16), rows,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    init = (jnp.full((b, s, h, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, s, h, 1), jnp.float32),
            jnp.zeros((b, s, h, 2 * w), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(tile, init, jnp.arange(c // tk))
    return acc / jnp.maximum(l, 1e-9)
