"""The paged cache of a latent-attention model (models/glm_dsa.py), held once,
and the XLA forms of attention over it.  Two layouts, chosen by what reads the
rows: a model with a sparse-attention indexer gathers single rows (the two
parts below), a model that attends to every cached row fetches whole blocks
(``init_dense_cache``, at the end of this docstring).

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
             layers that compute an index (``Lf`` of the ``L``).  A block of
             one layer is one contiguous [Bs, Di] DMA for the kernel that
             scores a decode step's keys where they lie
             (ops/pallas/dsa_index_scores.py).

Both index blocks on axis 1 and share the engine's block table, so a block
id is one block of every layer in both parts, and prefix reuse carries both.

The dense layout (no indexer, so no ``index_k`` and no single-row DMA):

``latent``   bf16 [L, N, Bs, Wd]: the row padded to whole 128-lane groups of
             bf16 (``dense_row_width``), nothing packed.  A block of one
             layer is one contiguous [Bs, Wd] tile-aligned DMA for the
             dense kernels (ops/pallas/mla_dense_attention.py); rows are
             written by XLA's own scatter, which keeps this plain layout
             in place (as it does for ``index_k``).  Mistral-Small-4: width
             320 -> Wd 384, 768 B a token and layer (640 of them data; the
             word layout above would take 1,024).
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
    "dense_row_width", "init_dense_cache", "write_dense", "dense_attention",
    "dense_decode_groups",
    "index_decode_groups", "decode_index_scores",
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


def dense_row_width(width: int) -> int:
    """Elements of a dense-layout cache row that holds ``width``."""
    return LANES * math.ceil(width / LANES)


def init_dense_cache(num_layers: int, num_blocks: int, block_size: int,
                     width: int, dtype):
    return {"latent": jnp.zeros(
        (num_layers, num_blocks, block_size, dense_row_width(width)), dtype)}


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


def write_dense(latent: jax.Array, layer, rows: jax.Array,
                slots: jax.Array) -> jax.Array:
    """Write ``rows`` [T, width] of one layer of the dense layout at flat
    token slots [T] (a negative slot writes nothing)."""
    return write_rows(latent, layer, _pad_to(rows, latent.shape[-1]), slots)


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
                     sm_scale: float, dv: int, live: jax.Array,
                     seq_lens: jax.Array) -> jax.Array:
    """Attention of q [B, S, H, width] over the first C positions of each
    sequence's block table, restricted to ``mask`` [B, S, C]: f32
    [B, S, H, dv'] whose first ``dv`` elements are the weighted sum of the
    rows' first ``dv``.  ``live`` [B] says how many of a sequence's S tokens
    exist (the first ones) and ``seq_lens`` [B] how many of the C positions:
    the mask holds nothing past either, and the kernel does no work there.
    On the TPU, one sequence at a time, the kernel of
    ops/pallas/mla_masked_prefill.py over a context padded to whole tiles of
    keys (blocks named twice, masked); else ``dense_masked_attention``."""
    from dynamo_tpu.ops.pallas.registry import (
        MLA_MASKED_KEYS_PER_TILE,
        MLA_MASKED_TOKENS_PER_TILE,
    )

    b, s, h, width = q.shape
    c = mask.shape[-1]
    bs = latent.shape[2]
    dq, dvp = -(-width // LANES) * LANES, -(-dv // LANES) * LANES
    # keys a grid step: the registry's tile, or all of a shorter context
    tk = min(MLA_MASKED_KEYS_PER_TILE, -(-c // LANES) * LANES)
    if not (kernels_on() and b == 1 and s % 8 == 0 and tk % bs == 0):
        return dense_masked_attention(
            q, context_rows(latent, layer, block_tables), mask, sm_scale)
    from dynamo_tpu.ops.pallas.mla_masked_prefill import (
        mla_sparse_prefill_masked,
    )

    pad = -c % tk
    context = context_rows(
        latent, layer, jnp.pad(block_tables, ((0, 0), (0, pad // bs))))
    bias = jnp.where(jnp.pad(mask[0], ((0, 0), (0, pad))), 0.0, NEG_INF)
    tq = max(d for d in (MLA_MASKED_TOKENS_PER_TILE, 8) if s % d == 0)
    out = mla_sparse_prefill_masked(
        _pad_to(q[0], dq).reshape(s * h, dq), context[0, :, :dq],
        bias.astype(jnp.float32), jnp.stack([live[0], seq_lens[0]]),
        heads=h, dv=dvp, sm_scale=sm_scale, tokens_per_tile=tq,
        keys_per_tile=tk)
    return out.reshape(1, s, h, dvp)


def dense_masked_attention(q: jax.Array, context: jax.Array,
                           mask: jax.Array | None, sm_scale: float,
                           tile_tokens: int = 256, *, positions=None,
                           seq_lens=None) -> jax.Array:
    """Attention of q [B, S, H, width] over ``context`` [B, C, D] (the rows
    of each sequence's first C tokens, zero padded to D), restricted to
    ``mask`` [B, S, C] (the selected and causal positions) — or, with
    ``mask`` None, to what is causal alone: key c for the query at
    ``positions`` [B, S] if c <= its position and c < ``seq_lens`` [B],
    worked out a tile at a time, so no [S, C] array is built for a layer
    whose mask is only causality.  The context is read a tile at a time with
    a running softmax, so no [S, H, C] array exists.  Returns f32
    [B, S, H, D].  This is how a long prefill chunk attends: every key is
    scored once for all the chunk's queries on the matrix unit, where a
    gather would fetch each query's rows separately."""
    b, s, h, _ = q.shape
    c, d = context.shape[1:]
    tk = max(n for n in range(1, min(tile_tokens, c) + 1) if c % n == 0)
    q = _pad_to(q, d).astype(jnp.bfloat16)

    def tile(carry, t):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(context, t * tk, tk, axis=1)
        if mask is not None:
            ok = jax.lax.dynamic_slice_in_dim(mask, t * tk, tk, axis=2)
        else:
            at = t * tk + jnp.arange(tk, dtype=jnp.int32)
            ok = ((at <= positions[:, :, None])
                  & (at < seq_lens[:, None, None]))
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
            jnp.zeros((b, s, h, d), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(tile, init, jnp.arange(c // tk))
    return acc / jnp.maximum(l, 1e-9)


def _decode_lens(positions: jax.Array, seq_lens: jax.Array) -> jax.Array:
    """Rows each query of a decode step sees: a row past its limit has a
    length at or under its position."""
    return jnp.minimum(seq_lens, positions[:, 0] + 1)


def dense_decode_groups(block_tables: jax.Array, positions: jax.Array,
                        seq_lens: jax.Array, block_size: int):
    """What ``dense_attention`` takes as ``groups`` for one query a row: the
    rows of a decode step that hold the same leading blocks, worked out once
    a step for every layer's ``mla_dense_decode``; None where the XLA form
    attends (it knows no groups)."""
    if not kernels_on():
        return None
    from dynamo_tpu.ops.pallas.mla_dense_attention import decode_groups

    return decode_groups(jnp, block_tables, _decode_lens(positions, seq_lens),
                         block_size)


def index_decode_groups(index_k: jax.Array, heads: int,
                        block_tables: jax.Array, positions: jax.Array,
                        seq_lens: jax.Array):
    """What ``decode_index_scores`` takes as ``groups`` for one query a row:
    the rows of a decode step that hold the same leading blocks, worked out
    once a step for every ``full`` layer's ``dsa_index_scores``; None where
    the indexer gathers its keys and XLA scores them (no kernels, or a step
    whose scores the kernel cannot hold)."""
    if not kernels_on():
        return None
    from dynamo_tpu.ops.pallas import dsa_index_scores as dsa
    from dynamo_tpu.ops.pallas.mla_dense_attention import decode_groups
    from dynamo_tpu.ops.pallas.registry import (
        DSA_INDEX_BLOCKS_PER_CHUNK,
        DSA_INDEX_GROUP_ROWS,
    )

    (b, m), (_, _, bs, di) = block_tables.shape, index_k.shape
    if not dsa.fits(b, m, bs, heads, di):
        return None
    return decode_groups(jnp, block_tables, _decode_lens(positions, seq_lens),
                         bs, DSA_INDEX_BLOCKS_PER_CHUNK, DSA_INDEX_GROUP_ROWS)


def decode_index_scores(q: jax.Array, w: jax.Array, index_k: jax.Array,
                        layer, block_tables: jax.Array, positions: jax.Array,
                        seq_lens: jax.Array, groups: jax.Array) -> jax.Array:
    """The index scores of a decode step by ``dsa_index_scores``: q
    [B, Hi, Di] and w [B, Hi], one query a row, over the keys of row
    ``layer`` of ``index_k`` that ``block_tables`` [B, M] names, with the
    step's ``groups`` (``index_decode_groups``: not None).  f32 [B, M·Bs],
    a row's scores at the positions it sees (c <= its position, c <
    ``seq_lens``) and anything elsewhere."""
    from dynamo_tpu.ops.pallas.dsa_index_scores import dsa_index_scores

    lf, n, bs, di = index_k.shape
    return dsa_index_scores(
        q, w, index_k.reshape(lf * n, bs, di), block_tables + layer * n,
        _decode_lens(positions, seq_lens), groups)


def dense_attention(q: jax.Array, latent: jax.Array, layer,
                    block_tables: jax.Array, positions: jax.Array,
                    seq_lens: jax.Array, dv: int, groups=None) -> jax.Array:
    """Causal attention of q [B, S, H, width] — already scaled — over every
    cached row of each sequence (the dense layout, ``init_dense_cache``):
    the query at ``positions`` [B, S] sees the rows c <= its position, c <
    ``seq_lens`` [B], of the blocks ``block_tables`` [B, Mc].  f32
    [B, S, H, dv'], dv' >= dv whole lane groups: the weighted sum of the
    rows' first elements.  On the TPU the kernels of
    ops/pallas/mla_dense_attention.py (``mla_dense_decode`` for one query a
    row, over the step's ``groups`` — ``dense_decode_groups`` — or its own;
    ``mla_dense_prefill`` a sequence at a time otherwise); else the tiled
    XLA form, which is also their oracle."""
    l, n, bs, wd = latent.shape
    b, s, h, _ = q.shape
    dvp = -(-dv // LANES) * LANES
    q = _pad_to(q, wd).astype(latent.dtype)
    if not kernels_on():
        context = latent[layer, block_tables].reshape(b, -1, wd)
        return dense_masked_attention(
            q, context, None, 1.0, positions=positions,
            seq_lens=seq_lens)[..., :dvp]
    from dynamo_tpu.ops.pallas import mla_dense_attention as dense

    flat = latent.reshape(l * n, bs, wd)
    if s == 1:
        return dense.mla_dense_decode(
            q[:, 0], flat, block_tables + layer * n,
            _decode_lens(positions, seq_lens), groups, dv=dvp)[:, None]
    return jnp.stack([
        dense.mla_dense_prefill(
            q[i].reshape(s * h, wd), flat, block_tables[i] + layer * n,
            jnp.stack([positions[i, 0], seq_lens[i]]), heads=h, dv=dvp,
        ).reshape(s, h, dvp) for i in range(b)])
