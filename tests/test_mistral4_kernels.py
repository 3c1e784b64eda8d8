"""The dense latent-attention kernels (ops/pallas/mla_dense_attention.py) in
interpret mode against the XLA form of ops/latent_cache.py, which is the CPU
path and their oracle.  Interpret mode fills scratch no copy wrote with NaN,
so a block fetched past a row's length, or a stale row multiplied instead of
selected away, shows."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import latent_cache
from dynamo_tpu.ops.pallas.mla_dense_attention import (
    mla_dense_decode,
    mla_dense_prefill,
)

L, N, BS, WIDTH, H, DV = 2, 40, 8, 80, 4, 128
WD = latent_cache.dense_row_width(WIDTH)          # 128


def pool(seed: int, poison=()):
    """A two-layer dense cache of finite rows; the blocks in ``poison`` hold
    NaN: a kernel that so much as multiplies one of them by zero shows."""
    rng = np.random.default_rng(seed)
    rows = rng.normal(size=(L, N, BS, WD)).astype(np.float32)
    rows[..., WIDTH:] = 0
    for layer, block in poison:
        rows[layer, block] = np.nan
    return jnp.asarray(rows, jnp.bfloat16)


def queries(seed: int, *shape):
    q = np.random.default_rng(seed).normal(size=(*shape, WIDTH)) * 0.3
    return jnp.asarray(q, jnp.bfloat16)


def oracle(q, latent, layer, tables, positions, lens):
    """``dense_attention``'s XLA branch (the backend here is the CPU)."""
    return np.asarray(latent_cache.dense_attention(
        q, latent, layer, jnp.asarray(tables), jnp.asarray(positions),
        jnp.asarray(lens), dv=DV))


@pytest.mark.parametrize("chunk", [2, 16], ids=["two-block-chunks", "one-chunk"])
def test_decode_walks_each_rows_own_blocks(chunk):
    """Ragged lengths: an empty slot, one token, a context that ends inside
    a block, one that ends on a block's edge, one that fills several chunks.
    Blocks a row does not own are NaN in the pool (and NaN in the scratch
    where nothing was copied): the result is finite and the oracle's."""
    lens = np.asarray([0, 1, 13, 16, 61, 37], np.int32)
    tables = np.zeros((6, 10), np.int32)
    free = iter(range(1, N))
    owned = set()
    for r, n in enumerate(lens):
        for j in range(-(-int(n) // BS)):
            tables[r, j] = next(free)
            owned.add(tables[r, j])
    layer = 1
    latent = pool(0, poison=[(layer, b) for b in range(N) if b not in owned]
                  + [(0, b) for b in range(N)])
    q = queries(1, 6, 1, H)
    got = np.asarray(mla_dense_decode(
        latent_cache._pad_to(q[:, 0], WD), latent.reshape(L * N, BS, WD),
        jnp.asarray(tables + layer * N), jnp.asarray(lens), dv=DV,
        blocks_per_chunk=chunk, interpret=True))
    assert np.isfinite(got).all()
    assert (got[0] == 0).all()                       # the empty slot
    clean = jnp.nan_to_num(latent)                   # the oracle reads 0 x p
    ref = oracle(q, clean, layer, tables, (lens - 1).clip(0)[:, None], lens)
    np.testing.assert_allclose(got[1:], ref[1:, 0], atol=2e-2)


@pytest.mark.parametrize("start,take,padded", [
    (0, 40, 48), (64, 21, 32), (32, 32, 32)],
    ids=["cold", "after-a-prefix", "whole-blocks"])
def test_prefill_is_causal_by_position_inside_the_kernel(start, take, padded):
    """A chunk with and without a cached prefix, its tail padded to a
    bucket, against a context gathered to whole key tiles: tiles of 16
    tokens x 4 heads meet tiles of 16 keys, so some tiles lie wholly before
    the chunk, some cross the diagonal, some lie wholly after it."""
    n = start + take
    # 13 blocks, gathered as 14 (whole tiles of two: the pad is row 0 of
    # the flat pool, a finite row like any other, masked by the length)
    table = np.arange(3, 3 + 13, dtype=np.int32)
    layer = 1
    # every other layer's rows must weigh nothing
    latent = pool(2, poison=[(0, b) for b in range(1, N)])
    q = queries(3, 1, padded, H)
    at = jnp.asarray([start, n], jnp.int32)
    got = np.asarray(mla_dense_prefill(
        latent_cache._pad_to(q[0], WD).reshape(padded * H, WD),
        latent.reshape(L * N, BS, WD), jnp.asarray(table + layer * N), at,
        heads=H, dv=DV, rows_per_tile=16 * H, keys_per_tile=16,
        interpret=True)).reshape(padded, H, DV)
    assert np.isfinite(got).all()
    positions = (start + np.arange(padded, dtype=np.int32))[None]
    ref = oracle(q, latent, layer, table[None], positions, [n])
    np.testing.assert_allclose(got[:take], ref[0, :take], atol=2e-2)


def test_causal_xla_form_builds_no_mask_and_equals_the_masked_one():
    """``dense_masked_attention`` with no mask works causality out a tile
    at a time; the same numbers as handing it the [S, C] mask."""
    rng = np.random.default_rng(4)
    ctx = jnp.asarray(rng.normal(size=(2, 96, WD)), jnp.bfloat16)
    q = queries(5, 2, 12, H)
    positions = jnp.asarray([np.arange(40, 52), np.arange(80, 92)], jnp.int32)
    lens = jnp.asarray([52, 90], jnp.int32)
    at = jnp.arange(96)
    mask = ((at[None, None, :] <= positions[:, :, None])
            & (at[None, None, :] < lens[:, None, None]))
    with_mask = latent_cache.dense_masked_attention(q, ctx, mask, 0.25,
                                                    tile_tokens=32)
    causal = latent_cache.dense_masked_attention(
        q, ctx, None, 0.25, tile_tokens=32, positions=positions,
        seq_lens=lens)
    np.testing.assert_array_equal(np.asarray(with_mask), np.asarray(causal))
