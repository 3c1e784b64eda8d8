"""``write_kv_cache_layer``'s block-granular path against its row path.

A chunk of exactly one block (every prompt of up to ``block_size`` tokens)
is written by one dynamic-update-slice, not by a scatter of one update: XLA
lowered that scatter to a select over the whole cache, a second copy of it
among the prefill program's temporaries (ROADMAP S7; the compile for the
described v5e is in tests/test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.paged_attention import write_kv_cache_layer

L, N, BS, HK, D = 3, 6, 8, 2, 16


def _case(blocks: int, valid: int, block_ids, seed=0):
    rng = np.random.default_rng(seed)
    cache = jnp.asarray(rng.normal(size=(L, N, 2, BS, HK * D)), jnp.float32)
    s = blocks * BS
    k = jnp.asarray(rng.normal(size=(1, s, HK, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, HK, D)), jnp.float32)
    slots = np.full((1, s), -1, np.int32)
    for j in range(valid):
        slots[0, j] = block_ids[j // BS] * BS + j % BS
    return cache, k, v, jnp.asarray(slots)


@pytest.mark.parametrize("blocks,valid,block_ids", [
    (1, 8, [4]), (1, 5, [N - 1]), (1, 1, [0]), (1, 0, [2]),
    (2, 16, [1, 5]), (2, 11, [3, N - 1]), (2, 8, [2, 0])])
@pytest.mark.parametrize("layer", [0, L - 1])
def test_block_write_equals_row_write(blocks, valid, block_ids, layer):
    cache, k, v, slots = _case(blocks, valid, block_ids)
    write = jax.jit(write_kv_cache_layer, static_argnames=("block_aligned",))
    want = write(cache, layer, k, v, slots, block_aligned=False)
    got = write(cache, layer, k, v, slots, block_aligned=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    changed = np.asarray(want) != np.asarray(cache)
    assert changed.any() == (valid > 0)
    assert not np.delete(changed, layer, axis=0).any()


def test_one_block_write_lowers_to_no_scatter():
    cache, k, v, slots = _case(1, 5, [3])
    text = jax.jit(lambda c, k, v, s: write_kv_cache_layer(
        c, 1, k, v, s, block_aligned=True)).lower(cache, k, v, slots).as_text()
    assert "scatter" not in text and "dynamic_update_slice" in text
    two = _case(2, 16, [1, 5])
    text = jax.jit(lambda c, k, v, s: write_kv_cache_layer(
        c, 1, k, v, s, block_aligned=True)).lower(*two).as_text()
    assert "scatter" in text
