"""Pallas TPU kernels vs their pure-JAX oracles (interpret mode on CPU).

Mirrors the reference's pattern of testing engine kernels against a slow
reference implementation (SURVEY.md §4).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.paged_attention import paged_attention
from dynamo_tpu.ops.pallas.decode_attention import paged_decode_attention


def _mk_cache(rng, n_layers, n, bs, hk, d, dtype=jnp.float32):
    """Full multi-layer cache [L, N, 2, Bs, Hk*D] with random contents."""
    return jnp.asarray(
        rng.normal(size=(n_layers, n, 2, bs, hk * d)), dtype
    )


def _oracle(q, cache, layer, bt, seq_lens):
    l, n, _, bs, hkd = cache.shape
    b, _, h, d = q.shape
    hk = hkd // d
    kc = cache[layer, :, 0].reshape(n, bs, hk, d)
    vc = cache[layer, :, 1].reshape(n, bs, hk, d)
    positions = (seq_lens - 1)[:, None].astype(jnp.int32)
    return paged_attention(q, kc, vc, bt, seq_lens, positions)[:, 0]


@pytest.mark.parametrize(
    "b,h,hk,d,bs,n,m,c,layer",
    [
        (4, 8, 4, 64, 16, 32, 8, 8, 0),    # GQA, chunk == table
        (2, 8, 8, 128, 16, 64, 16, 4, 1),  # MHA, multi-chunk, layer 1
        (3, 4, 1, 32, 16, 16, 4, 2, 0),    # MQA, tiny heads
        (1, 8, 2, 64, 16, 8, 5, 2, 2),     # M not divisible by C
    ],
)
def test_decode_kernel_matches_oracle(b, h, hk, d, bs, n, m, c, layer):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    cache = _mk_cache(rng, 3, n, bs, hk, d)
    ids = rng.permutation(n)[: min(b * m, n)]
    bt = jnp.asarray(np.resize(ids, (b, m)).astype(np.int32))
    lens = rng.integers(1, m * bs + 1, size=b).astype(np.int32)
    lens[0] = 1  # boundary: single-token context
    seq_lens = jnp.asarray(lens)

    ref = _oracle(q, cache, layer, bt, seq_lens)
    out = paged_decode_attention(
        q[:, 0], cache, jnp.int32(layer), bt, seq_lens,
        blocks_per_chunk=c, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blocked_cache_write_preserves_padding_rows():
    """write_kv_cache_layer(block_aligned=True) must honor '-1 = drop'
    bit-for-bit: padding rows inside a partially-filled block keep the
    existing cache content, matching the row path exactly."""
    from dynamo_tpu.ops.paged_attention import write_kv_cache_layer

    rng = np.random.default_rng(3)
    l_, n, bs, hk, d = 2, 8, 16, 2, 32
    cache = _mk_cache(rng, l_, n, bs, hk, d)
    b, s = 1, 32  # two blocks; second block only 4 valid rows
    k_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    slot = np.full((b, s), -1, np.int32)
    take = 20
    bids = [5, 2]
    pos = np.arange(take)
    slot[0, :take] = np.asarray(bids)[pos // bs] * bs + pos % bs
    slot = jnp.asarray(slot)

    row = write_kv_cache_layer(cache, jnp.int32(1), k_new, v_new, slot)
    blk = write_kv_cache_layer(cache, jnp.int32(1), k_new, v_new, slot,
                               block_aligned=True)
    np.testing.assert_array_equal(np.asarray(row), np.asarray(blk))


# ----------------------------------------------------------- flash prefill


def _prefill_oracle(q, k_new, v_new, cache, layer, bt, seq_lens, start,
                    prefix_blocks):
    """Pure-JAX reference.  MUST pin the pure path: on TPU,
    prefill_attention dispatches to the very kernel under test — without
    the env pin this test would compare the kernel against itself."""
    import os

    from dynamo_tpu.ops.paged_attention import prefill_attention

    os.environ["DYNAMO_DISABLE_PALLAS_PREFILL"] = "1"
    try:
        return prefill_attention(
            q, k_new, v_new, cache, jnp.int32(layer), bt, seq_lens,
            start, prefix_blocks,
        )
    finally:
        os.environ.pop("DYNAMO_DISABLE_PALLAS_PREFILL", None)


@pytest.mark.parametrize(
    "b,s,h,hk,d,bs,prefix_blks,tq,c,layer",
    [
        (1, 64, 8, 4, 64, 16, 0, 32, 4, 0),   # no prefix, multi row-chunk
        (1, 64, 8, 4, 64, 16, 4, 32, 2, 1),   # cached prefix, GQA
        (2, 32, 4, 4, 32, 16, 2, 32, 2, 0),   # batch, MHA, single row-chunk
        (1, 48, 8, 2, 64, 16, 3, 16, 8, 2),   # S not power of two, tq halves
        (1, 32, 4, 1, 32, 16, 5, 32, 2, 0),   # MQA, prefix > one DMA chunk
    ],
)
def test_prefill_kernel_matches_oracle(b, s, h, hk, d, bs, prefix_blks,
                                       tq, c, layer):
    from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    n = 64
    cache = _mk_cache(rng, 3, n, bs, hk, d)
    m = prefix_blks + s // bs + 1
    bt = jnp.asarray(
        np.resize(rng.permutation(n), (b, m)).astype(np.int32)
    )
    start = jnp.full((b,), prefix_blks * bs, jnp.int32)
    # row 0 exercises padding: fewer fresh tokens than S
    fresh = np.full(b, s, np.int32)
    fresh[0] = max(1, s - 7)
    seq_lens = jnp.asarray(start + fresh)

    ref = _prefill_oracle(q, k_new, v_new, cache, layer, bt, seq_lens,
                          start, prefix_blks)
    out = paged_prefill_attention(
        q, k_new, v_new, cache, jnp.int32(layer), bt, seq_lens, start,
        rows_per_chunk=tq, blocks_per_chunk=c, interpret=True,
    )
    # compare only the valid (non-padding) rows of each batch entry
    for i in range(b):
        f = int(fresh[i])
        np.testing.assert_allclose(
            np.asarray(out)[i, :f], np.asarray(ref)[i, :f],
            atol=2e-5, rtol=1e-5,
        )


def test_prefill_kernel_padding_rows_finite():
    from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(1)
    b, s, h, hk, d, bs = 1, 32, 4, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    cache = _mk_cache(rng, 1, 8, bs, hk, d)
    bt = jnp.zeros((b, 4), jnp.int32)
    out = paged_prefill_attention(
        q, k_new, v_new, cache, jnp.int32(0), bt,
        jnp.asarray([5], jnp.int32), jnp.asarray([0], jnp.int32),
        interpret=True,
    )
    arr = np.asarray(out)
    # padding rows flow through the rest of the network before being
    # discarded at last_idx — they must be finite (never NaN/inf)
    assert np.isfinite(arr).all()


def test_decode_kernel_zero_len_rows_are_zero():
    rng = np.random.default_rng(0)
    b, h, hk, d, bs, n, m = 2, 4, 2, 32, 16, 8, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    cache = _mk_cache(rng, 1, n, bs, hk, d)
    bt = jnp.zeros((b, m), jnp.int32)
    seq_lens = jnp.asarray([0, 5], jnp.int32)
    out = np.asarray(
        paged_decode_attention(q, cache, jnp.int32(0), bt, seq_lens, interpret=True)
    )
    assert np.all(out[0] == 0.0)
    assert np.all(np.isfinite(out))


def test_decode_kernel_bf16_cache():
    rng = np.random.default_rng(1)
    b, h, hk, d, bs, n, m = 2, 8, 4, 64, 16, 16, 4
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.bfloat16)
    cache = _mk_cache(rng, 2, n, bs, hk, d, jnp.bfloat16)
    bt = jnp.asarray(np.arange(b * m).reshape(b, m).astype(np.int32))
    seq_lens = jnp.asarray([33, 64], jnp.int32)
    ref = _oracle(q, cache, 1, bt, seq_lens)
    out = paged_decode_attention(
        q[:, 0], cache, jnp.int32(1), bt, seq_lens, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_decode_kernel_logit_softcap_matches_oracle():
    """Gemma2 attention score softcap inside the flash-decode kernel."""
    rng = np.random.default_rng(11)
    b, h, hk, d, bs, n, m, cap = 2, 8, 4, 64, 16, 32, 8, 50.0
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)) * 3, jnp.float32)
    cache = _mk_cache(rng, 2, n, bs, hk, d)
    bt = jnp.asarray(np.resize(rng.permutation(n), (b, m)).astype(np.int32))
    seq_lens = jnp.asarray([5, m * bs], jnp.int32)

    l_, n_, _, bs_, hkd = cache.shape
    kc = cache[1, :, 0].reshape(n_, bs_, hk, d)
    vc = cache[1, :, 1].reshape(n_, bs_, hk, d)
    ref = paged_attention(q, kc, vc, bt, seq_lens,
                          (seq_lens - 1)[:, None].astype(jnp.int32),
                          logit_cap=cap)[:, 0]
    from dynamo_tpu.ops.pallas.decode_attention import paged_decode_attention

    got = paged_decode_attention(
        q[:, 0], cache, jnp.int32(1), bt, seq_lens, logit_cap=cap,
        blocks_per_chunk=4, seqs_per_group=2, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_prefill_kernel_logit_softcap_matches_oracle():
    import os

    from dynamo_tpu.ops.paged_attention import prefill_attention
    from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention

    rng = np.random.default_rng(12)
    b, s, h, hk, d, bs, cap = 2, 32, 4, 2, 32, 16, 30.0
    n = 8
    cache = _mk_cache(rng, 1, n, bs, hk, d)
    bt = jnp.asarray(np.arange(b * 4).reshape(b, 4).astype(np.int32))
    prefix = 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)) * 2, jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    seq_lens = jnp.asarray([prefix + s, prefix + s - 3], jnp.int32)
    start = jnp.full((b,), prefix, jnp.int32)
    os.environ["DYNAMO_DISABLE_PALLAS"] = "1"
    try:
        ref = prefill_attention(q, kn, vn, cache, jnp.int32(0), bt, seq_lens,
                                start, prefix_blocks=1, logit_cap=cap)
    finally:
        del os.environ["DYNAMO_DISABLE_PALLAS"]
    got = paged_prefill_attention(q, kn, vn, cache, jnp.int32(0), bt,
                                  seq_lens, start, logit_cap=cap,
                                  rows_per_chunk=16, blocks_per_chunk=2,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)


def test_mq_decode_kernel_matches_oracle():
    """Multi-query flash decode (speculative verify shape): S trailing
    queries per row, variable real query counts, vs the padded oracle."""
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    rng = np.random.default_rng(21)
    b, s, h, hk, d, bs, n, m = 4, 4, 8, 4, 64, 16, 32, 8
    cache = _mk_cache(rng, 2, n, bs, hk, d)
    bt = jnp.asarray(np.resize(rng.permutation(n), (b, m)).astype(np.int32))
    # per-row context lengths; queries are the TRAILING s positions
    lens = np.asarray([5, 17, 64, 128], np.int32)
    q0 = lens - s  # first query position
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    positions = jnp.asarray(q0[:, None] + np.arange(s)[None, :], jnp.int32)

    ref = paged_attention(
        q,
        cache[1, :, 0].reshape(n, bs, hk, d),
        cache[1, :, 1].reshape(n, bs, hk, d),
        bt, jnp.asarray(lens), positions,
    )
    got = paged_decode_attention_mq(
        q, cache, jnp.int32(1), bt, jnp.asarray(lens), jnp.asarray(q0),
        blocks_per_chunk=2, seqs_per_group=4, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)


def test_mq_decode_kernel_quant_and_softcap():
    """MQ kernel with the int8 cache and a Gemma2-style score softcap."""
    from dynamo_tpu.ops.kv_quant import (
        QuantKvCache, dequant_layer_slice, pad_scales,
    )
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    rng = np.random.default_rng(22)
    b, s, h, hk, d, bs, n, m, cap = 2, 3, 4, 2, 32, 16, 16, 4, 30.0
    data = jnp.asarray(
        rng.integers(-127, 127, size=(1, n, 2, bs, hk * d)), jnp.int8)
    scale = pad_scales(jnp.asarray(rng.random((1, n, 2, hk, bs)) * 0.05 + 0.01,
                                   jnp.float32))
    cache = QuantKvCache(data, scale)
    bt = jnp.asarray(np.arange(b * m).reshape(b, m).astype(np.int32))
    lens = np.asarray([s + 9, m * bs], np.int32)
    q0 = lens - s
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    positions = jnp.asarray(q0[:, None] + np.arange(s)[None, :], jnp.int32)

    layer_kv = dequant_layer_slice(cache.data[0], cache.scale[0], hk)
    ref = paged_attention(
        q,
        layer_kv[:, 0].reshape(n, bs, hk, d),
        layer_kv[:, 1].reshape(n, bs, hk, d),
        bt, jnp.asarray(lens), positions, logit_cap=cap,
    )
    got = paged_decode_attention_mq(
        q, cache, jnp.int32(0), bt, jnp.asarray(lens), jnp.asarray(q0),
        logit_cap=cap, blocks_per_chunk=2, seqs_per_group=2, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)


def test_int8_matmul_kernel_matches_xla_path():
    """Dequant-in-kernel matmul (interpret) vs the XLA int8 path."""
    from dynamo_tpu.models.quant import QTensor, matmul, quantize
    from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul

    rng = np.random.default_rng(31)
    m, k, n = 128, 512, 1024
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    q = quantize(w)
    ref = matmul(x, q)
    got = int8_matmul(x, q.q, jnp.squeeze(q.scale, axis=-2),
                      out_dtype=jnp.float32, interpret=True)
    # same int8 contents, but the kernel multiplies in bf16 on purpose
    # (that IS the speed path) while the f32 oracle rounds differently:
    # tolerance sized for bf16 accumulation over K=512
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=5e-2, atol=0.5)
    # odd M that doesn't tile: a bm that divides it still works
    got = int8_matmul(x[:64], q.q, jnp.squeeze(q.scale, axis=-2),
                      out_dtype=jnp.float32, bm=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref)[:64],
                               rtol=5e-2, atol=0.5)


def test_scale_tile_pad_invariants():
    """scale_tile rounds to the f32 (8, 128) tiling; pad_scales pads with
    the neutral scale 1.0 and is a no-op at tile-exact shapes."""
    from dynamo_tpu.ops.kv_quant import pad_scales, scale_tile

    assert scale_tile(8, 32) == (8, 128)
    assert scale_tile(4, 16) == (8, 128)
    assert scale_tile(8, 128) == (8, 128)
    assert scale_tile(16, 256) == (16, 256)
    sc = jnp.arange(2 * 3 * 2 * 4 * 16, dtype=jnp.float32).reshape(
        2, 3, 2, 4, 16)
    padded = pad_scales(sc)
    assert padded.shape == (2, 3, 2, 8, 128)
    np.testing.assert_array_equal(np.asarray(padded[..., :4, :16]),
                                  np.asarray(sc))
    assert float(padded[..., 4:, :].min()) == 1.0
    exact = jnp.ones((1, 2, 2, 8, 128), jnp.float32)
    assert pad_scales(exact) is exact


def test_kernels_at_8b_serving_geometry():
    """Both kernels at the EXACT 8B bench geometry (hk=8, d=128, bs=32,
    int8 KV with padded scales) in interpret mode — pins the shape logic
    the real chip runs; Mosaic-level lowering is covered by
    benchmarks/probe_kernels.py on hardware."""
    from dynamo_tpu.ops.kv_quant import QuantKvCache, pad_scales
    from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention
    from dynamo_tpu.ops.paged_attention import prefill_attention

    rng = np.random.default_rng(77)
    l, n, bs, hk, d, h = 1, 12, 32, 8, 128, 32
    b, m = 2, 3
    data = jnp.asarray(rng.integers(-127, 127, size=(l, n, 2, bs, hk * d)),
                       jnp.int8)
    scale = pad_scales(jnp.asarray(
        rng.random((l, n, 2, hk, bs)) * 0.05 + 0.01, jnp.float32))
    cache = QuantKvCache(data, scale)
    bt = jnp.asarray(np.arange(b * m).reshape(b, m).astype(np.int32))

    # decode at odd lengths
    lens = jnp.asarray([1, 2 * bs + 7], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.float32)
    layer_kv = __import__("dynamo_tpu.ops.kv_quant", fromlist=["x"]) \
        .dequant_layer_slice(cache.data[0], cache.scale[0], hk)
    ref = paged_attention(
        q, layer_kv[:, 0].reshape(n, bs, hk, d),
        layer_kv[:, 1].reshape(n, bs, hk, d), bt, lens,
        (lens - 1)[:, None].astype(jnp.int32))[:, 0]
    got = paged_decode_attention(
        q[:, 0], cache, jnp.int32(0), bt, lens,
        blocks_per_chunk=2, seqs_per_group=2, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)

    # prefill: one cached prefix block + 64 fresh rows
    s, prefix = 64, bs
    q2 = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    seq_lens = jnp.asarray([prefix + s, prefix + s - 9], jnp.int32)
    start = jnp.full((b,), prefix, jnp.int32)
    ref2 = prefill_attention(q2, kn, vn, cache, jnp.int32(0), bt, seq_lens,
                             start, prefix_blocks=1)
    got2 = paged_prefill_attention(q2, kn, vn, cache, jnp.int32(0), bt,
                                   seq_lens, start, rows_per_chunk=32,
                                   blocks_per_chunk=2, interpret=True)
    for i, f in enumerate([s, s - 9]):
        np.testing.assert_allclose(np.asarray(got2)[i, :f],
                                   np.asarray(ref2)[i, :f], atol=3e-5)


# ---------------------------------------------------- ragged flash prefill


def _mk_ragged(rng, takes, starts_l, bs, n, m, r_pad=None):
    """Pack per-row (take, start) specs onto a flat axis: returns
    (T, seq_ids [1,T], block_tables [R,M], seq_lens, starts, roff)."""
    r = len(takes) if r_pad is None else r_pad
    spans = [-(-tk // bs) * bs for tk in takes]
    t = sum(spans)
    seq_ids = np.full((1, t), -1, np.int32)
    roff = np.zeros(r, np.int32)
    starts = np.zeros(r, np.int32)
    seq_lens = np.zeros(r, np.int32)
    bt = np.zeros((r, m), np.int32)
    off = 0
    for i, (tk, st) in enumerate(zip(takes, starts_l)):
        seq_ids[0, off:off + tk] = i
        roff[i] = off
        starts[i] = st
        seq_lens[i] = st + tk
        bt[i] = (np.arange(m, dtype=np.int32) + i * m) % n
        off += spans[i]
    return (t, jnp.asarray(seq_ids), jnp.asarray(bt),
            jnp.asarray(seq_lens), jnp.asarray(starts), jnp.asarray(roff))


def _ragged_oracle(q, k_new, v_new, cache, layer, bt, seq_lens, starts,
                   roff, seq_ids, prefix_blocks):
    """Pure-JAX reference — pin the pure path (see _prefill_oracle)."""
    import os

    from dynamo_tpu.ops.paged_attention import ragged_prefill_attention

    os.environ["DYNAMO_DISABLE_PALLAS_PREFILL"] = "1"
    try:
        return ragged_prefill_attention(
            q, k_new, v_new, cache, jnp.int32(layer), bt, seq_lens,
            starts, roff, seq_ids, prefix_blocks,
        )
    finally:
        os.environ.pop("DYNAMO_DISABLE_PALLAS_PREFILL", None)


@pytest.mark.parametrize(
    "takes,starts_l,prefix_blks,tq,c,layer",
    [
        # three rows, no prefix; tiles straddle sequence boundaries
        ([40, 16, 50], [0, 0, 0], 0, 32, 2, 0),
        # mixed cached prefixes (per-row gathers + start masking)
        ([40, 16, 50], [32, 0, 16], 4, 32, 2, 1),
        # single row (degenerate ragged == plain prefill)
        ([64], [16], 1, 32, 4, 0),
        # many small rows inside one tile + padded row tail (r_pad > real)
        ([8, 8, 8, 8], [0, 16, 0, 32], 2, 16, 8, 2),
    ],
)
def test_ragged_prefill_kernel_matches_oracle(takes, starts_l, prefix_blks,
                                              tq, c, layer):
    from dynamo_tpu.ops.pallas.prefill_attention import (
        ragged_paged_prefill_attention,
    )

    rng = np.random.default_rng(11)
    hk, d, h, bs, n, m = 2, 32, 4, 16, 64, 8
    t, seq_ids, bt, seq_lens, starts, roff = _mk_ragged(
        rng, takes, starts_l, bs, n, m, r_pad=len(takes) + 1)
    q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
    cache = _mk_cache(rng, 3, n, bs, hk, d)

    ref = _ragged_oracle(q, k_new, v_new, cache, layer, bt, seq_lens,
                         starts, roff, seq_ids, prefix_blks)
    out = ragged_paged_prefill_attention(
        q, k_new, v_new, cache, jnp.int32(layer), bt, seq_lens, starts,
        roff, rows_per_chunk=tq, blocks_per_chunk=c, interpret=True,
    )
    # compare only real tokens: kernel and oracle agree there; padding
    # rows are finite garbage both discard (contracts differ in value)
    real = np.asarray(seq_ids)[0] >= 0
    np.testing.assert_allclose(
        np.asarray(out)[0][real], np.asarray(ref)[0][real],
        atol=2e-5, rtol=1e-5,
    )
    assert np.isfinite(np.asarray(out)).all()


def test_ragged_prefill_kernel_quant_geometry():
    """Ragged kernel against the int8 cache at the serving tile shape
    (bs=32, padded scales) — per-row prefix DMA must rescale like the
    base kernel."""
    from dynamo_tpu.ops.kv_quant import QuantKvCache, pad_scales
    from dynamo_tpu.ops.pallas.prefill_attention import (
        ragged_paged_prefill_attention,
    )

    rng = np.random.default_rng(13)
    l, n, bs, hk, d, h, m = 1, 16, 32, 2, 64, 4, 4
    data = jnp.asarray(rng.integers(-127, 127, size=(l, n, 2, bs, hk * d)),
                       jnp.int8)
    scale = pad_scales(jnp.asarray(
        rng.random((l, n, 2, hk, bs)) * 0.05 + 0.01, jnp.float32))
    cache = QuantKvCache(data, scale)
    t, seq_ids, bt, seq_lens, starts, roff = _mk_ragged(
        rng, [32, 64], [32, 64], bs, n, m)
    q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(1, t, hk, d)), jnp.float32)

    ref = _ragged_oracle(q, kn, vn, cache, 0, bt, seq_lens, starts, roff,
                         seq_ids, 2)
    out = ragged_paged_prefill_attention(
        q, kn, vn, cache, jnp.int32(0), bt, seq_lens, starts, roff,
        rows_per_chunk=32, blocks_per_chunk=2, interpret=True,
    )
    real = np.asarray(seq_ids)[0] >= 0
    np.testing.assert_allclose(
        np.asarray(out)[0][real], np.asarray(ref)[0][real], atol=3e-5,
    )


# ------------------------------------------------- registry audit matrix


# ------------------------------ a row's K/V traffic follows its own context
# (PR 40) A block is copied only if the row owns it; nothing is fetched for
# an empty slot; the scratch no copy wrote is stale (NaN in interpret mode).
# One kernel serves S = 1, S > 1 and the int8 cache: the same cases for each.
# Two sequences an update (PR 47): one that has ended runs masked beside one
# that has not.
_WALK = dict(b=8, h=4, hk=2, d=16, bs=8, m=8, g=4, c=2, r=2)  # 64 tokens
_WALK_LENS = {
    # one group holds a 1-token row and a whole-table row
    "ragged-1-to-table": [1, 64, 9, 33, 8, 17, 40, 63],
    # live rows among empty slots in one group, and a group of empty slots
    "live-among-empty": [0, 17, 0, 64, 0, 0, 0, 0],
}


def _walk_inputs(variant, lens, seed=40):
    """(q, cache, clean f32 cache of layer 1, bt, lens, q0) — ``variant`` is
    s1 / mq (three trailing queries a row) / int8 (S = 1, int8 K/V)."""
    from dynamo_tpu.ops.kv_quant import (
        QuantKvCache, dequant_layer_slice, pad_scales,
    )

    w = _WALK
    b, h, hk, d, bs, m = (w[k] for k in ("b", "h", "hk", "d", "bs", "m"))
    s = 3 if variant == "mq" else 1
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    lens = np.where(lens > 0, np.maximum(lens, s), 0).astype(np.int32)
    need = -(-lens // bs)
    n = int(need.sum()) + 6                 # block 0 and five more: no row's
    pool = rng.permutation(n - 1)[: need.sum()] + 1
    bt = np.zeros((b, m), np.int32)         # an empty slot: a table of zeros
    for r, at in enumerate(np.cumsum(need) - need):
        bt[r, : need[r]] = pool[at: at + need[r]]
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    if variant == "int8":
        data = jnp.asarray(
            rng.integers(-127, 127, size=(2, n, 2, bs, hk * d)), jnp.int8)
        scale = pad_scales(jnp.asarray(
            rng.random((2, n, 2, hk, bs)) * 0.05 + 0.01, jnp.float32))
        cache = QuantKvCache(data, scale)
        clean = dequant_layer_slice(data[1], scale[1], hk)
    else:
        cache = _mk_cache(rng, 2, n, bs, hk, d)
        clean = cache[1]
    return q, cache, clean, bt, lens, np.maximum(lens - s, 0)


def _walk_oracle(q, clean, bt, lens, q0):
    w = _WALK
    n, s = clean.shape[0], q.shape[1]
    kc = clean[:, 0].reshape(n, w["bs"], w["hk"], w["d"])
    vc = clean[:, 1].reshape(n, w["bs"], w["hk"], w["d"])
    positions = jnp.asarray(q0[:, None] + np.arange(s)[None, :], jnp.int32)
    return np.asarray(paged_attention(
        q, kc, vc, jnp.asarray(bt), jnp.asarray(lens), positions))


def _walk_kernel(q, cache, bt, lens, q0):
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    return np.asarray(paged_decode_attention_mq(
        q, cache, jnp.int32(1), jnp.asarray(bt), jnp.asarray(lens),
        jnp.asarray(q0), blocks_per_chunk=_WALK["c"],
        seqs_per_group=_WALK["g"], seqs_per_update=_WALK["r"],
        interpret=True))


def _poison_unowned(cache, bt, lens):
    """Every pool block no row owns: NaN, +inf, -inf (an int8 cache cannot
    hold them in its data: its scales do)."""
    from dynamo_tpu.ops.kv_quant import QuantKvCache, is_quant

    bs = _WALK["bs"]
    owned = {int(x) for r, n in enumerate(lens)
             for x in bt[r, : -(-int(n) // bs)]}
    vals = [np.nan, np.inf, -np.inf]
    arr = np.array(cache.scale if is_quant(cache) else cache)
    dead = [blk for blk in range(arr.shape[1]) if blk not in owned]
    assert 0 in dead and len(dead) >= 6
    for blk in dead:
        arr[:, blk] = vals[blk % 3]
    arr = jnp.asarray(arr)
    return QuantKvCache(cache.data, arr) if is_quant(cache) else arr


@pytest.mark.parametrize("variant", ["s1", "mq", "int8"])
@pytest.mark.parametrize("scenario", [
    "ragged-1-to-table", "live-among-empty", "unowned-blocks-poisoned",
    "rows-shuffled", "rows-grouped-by-length"])
def test_decode_kernel_walks_each_rows_own_blocks(scenario, variant):
    from dynamo_tpu.ops.paged_attention import rows_by_length

    lens = _WALK_LENS.get(scenario, [5, 0, 64, 23, 0, 41, 8, 23])
    q, cache, clean, bt, lens, q0 = _walk_inputs(variant, lens)
    live = lens > 0
    ref = _walk_oracle(q, clean, bt, lens, q0)
    if scenario == "unowned-blocks-poisoned":
        cache = _poison_unowned(cache, bt, lens)
    out = _walk_kernel(q, cache, bt, lens, q0)
    assert np.isfinite(out).all()
    assert (out[~live] == 0).all()
    np.testing.assert_allclose(out[live], ref[live], atol=3e-5)
    if scenario in ("rows-shuffled", "rows-grouped-by-length"):
        # a row's output is bit for bit the same whichever rows share its
        # group: in another order of the slots, and in the order the model
        # hands a decode step over (longest first, empty slots last)
        if scenario == "rows-shuffled":
            order = np.random.default_rng(7).permutation(len(lens))
            inverse = np.argsort(order)
        else:
            order, inverse = (np.asarray(a) for a in
                              rows_by_length(jnp.asarray(lens)))
            assert (np.diff(lens[order]) <= 0).all()
            assert order.tolist() == [2, 5, 3, 7, 6, 0, 1, 4]   # ties: by slot
        moved = _walk_kernel(q[order], cache, bt[order], lens[order],
                             q0[order])
        assert (order[inverse] == np.arange(len(lens))).all()
        np.testing.assert_array_equal(moved[inverse], out)


@pytest.mark.parametrize("mix", ["equal", "ragged", "mostly-empty"])
def test_decode_kernel_cost_counts_each_rows_own_blocks(mix):
    """``decode_kernel_cost``'s bytes are the sum over the rows of
    ceil(len / Bs) blocks, plus q in and the output back (both in the
    query's dtype); whatever the grouping, nothing for an empty slot, no rounding up to a chunk."""
    from dynamo_tpu.ops.pallas.registry import decode_kernel_cost

    b, h, hk, d, bs, m, c = 16, 8, 2, 128, 32, 16, 4
    lens = {"equal": [200] * b,
            "ragged": [1, 512, 33, 64, 65, 127, 300, 7] * 2,
            "mostly-empty": [0] * 13 + [257, 31, 512]}[mix]
    block_bytes = 2 * bs * hk * d * 2
    blocks = sum(-(-n // bs) for n in lens)
    cost = decode_kernel_cost(b, 1, h, hk, d, bs, m, lens, cache_bytes=2,
                              q_bytes=2, blocks_per_chunk=c)
    assert cost["hbm_bytes"] == blocks * block_bytes + b * h * hk * d * (2 + 2)
    # the matmuls take whole chunks of C blocks, a row's own
    chunks = sum(-(-n // (c * bs)) for n in lens)
    assert cost["flops"] == chunks * 4 * h * (c * bs) * hk * d
    # the int8 cache: a block's scale tile rides with it
    from dynamo_tpu.ops.kv_quant import scale_tile

    hp, sp = scale_tile(hk, bs)
    quant = decode_kernel_cost(b, 1, h, hk, d, bs, m, lens, cache_bytes=1,
                               quant=True, q_bytes=2, blocks_per_chunk=c)
    assert quant["hbm_bytes"] == (
        blocks * (block_bytes // 2 + 2 * hp * sp * 4) + b * h * hk * d * 4)


# ------------------------- bf16 operands, a row-chunk sized by its bytes
# (PR 47) Both matmuls take bf16 as the cache holds it and accumulate in
# float32; p reaches the matrix unit as a bf16 head and a bf16 remainder.
# Serving dtypes (bf16 q, bf16 or int8 cache) at the four cell geometries'
# lanes and the tiling ``decode_tiling`` gives each, against the float32
# oracle on the same values.  ``_PARENT_WORST``: what the float32-operand
# kernel of the parent commit (da567be, its own tiling) read on the same
# inputs, interpret mode — the new kernel may be no further off.
_PARITY_GEOMS = {256: (8, 2), 512: (32, 4), 1024: (32, 8), 2048: (16, 16)}
_PARITY_LENS = {256: (4096, 1), 512: (1, 2917), 1024: (3333, 97),
                2048: (640, 4096)}
_PARENT_WORST = {
    (256, "s1"): 0.0002147778868675232,
    (256, "s3"): 0.007281303405761719,
    (256, "int8"): 0.0037190914154052734,
    (256, "softcap"): 0.00023202598094940186,
    (512, "s1"): 0.00024375319480895996,
    (512, "s3"): 0.007747173309326172,
    (512, "int8"): 0.007512092590332031,
    (512, "softcap"): 0.00024268031120300293,
    (1024, "s1"): 0.0016565322875976562,
    (1024, "s3"): 0.001893758773803711,
    (1024, "int8"): 0.0019450783729553223,
    (1024, "softcap"): 0.0019453167915344238,
    (2048, "s1"): 0.000943988561630249,
    (2048, "s3"): 0.0007906854152679443,
    (2048, "int8"): 0.0009748935699462891,
    (2048, "softcap"): 0.0008189678192138672,
}


def _parity_inputs(lanes, variant):
    """(q, cache, clean f32 [N, 2, Bs, HkD], bt, lens, q0, logit_cap): 8
    slots of which two are live, every unowned pool block NaN / +-inf."""
    from dynamo_tpu.ops.kv_quant import (
        QuantKvCache, dequant_layer_slice, pad_scales,
    )

    (h, hk), d, bs, m, b = _PARITY_GEOMS[lanes], 128, 32, 128, 8
    s = 3 if variant == "s3" else 1
    rng = np.random.default_rng(47 + lanes)
    lens = np.zeros(b, np.int32)
    lens[[5, 2]] = np.maximum(_PARITY_LENS[lanes], s)
    need = -(-lens // bs)
    n = int(need.sum()) + 6
    pool = rng.permutation(n - 1)[: need.sum()] + 1
    bt = np.zeros((b, m), np.int32)
    for r, at in enumerate(np.cumsum(need) - need):
        bt[r, : need[r]] = pool[at: at + need[r]]
    dead = sorted(set(range(n)) - set(pool.tolist()))
    assert 0 in dead and len(dead) == 6
    poison = np.asarray([np.nan, np.inf, -np.inf], np.float32)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.bfloat16)
    if variant == "int8":
        data = jnp.asarray(
            rng.integers(-127, 128, size=(1, n, 2, bs, hk * d)), jnp.int8)
        scale = rng.random((1, n, 2, hk, bs)).astype(np.float32) * .02 + .002
        clean = dequant_layer_slice(
            data[0], pad_scales(jnp.asarray(scale))[0], hk)
        for i, blk in enumerate(dead):
            scale[:, blk] = poison[i % 3]
        cache = QuantKvCache(data, pad_scales(jnp.asarray(scale)))
    else:
        cache = jnp.asarray(rng.normal(size=(1, n, 2, bs, hk * d)),
                            jnp.bfloat16)
        clean = cache[0].astype(jnp.float32)
        for i, blk in enumerate(dead):
            cache = cache.at[:, blk].set(jnp.bfloat16(poison[i % 3]))
    cap = 30.0 if variant == "softcap" else None
    return q, cache, clean, bt, lens, np.maximum(lens - s, 0), cap


def _parity_worst(kernel, lanes, variant, interpret=True):
    """Worst |kernel - float32 oracle| over the live rows' outputs."""
    (h, hk), d, bs = _PARITY_GEOMS[lanes], 128, 32
    q, cache, clean, bt, lens, q0, cap = _parity_inputs(lanes, variant)
    n, s = clean.shape[0], q.shape[1]
    positions = jnp.asarray(q0[:, None] + np.arange(s)[None, :], jnp.int32)
    ref = np.asarray(paged_attention(
        q.astype(jnp.float32), clean[:, 0].reshape(n, bs, hk, d),
        clean[:, 1].reshape(n, bs, hk, d), jnp.asarray(bt),
        jnp.asarray(lens), positions, logit_cap=cap))
    out = np.asarray(kernel(
        q, cache, jnp.int32(0), jnp.asarray(bt), jnp.asarray(lens),
        jnp.asarray(q0), logit_cap=cap, interpret=interpret), np.float32)
    live = lens > 0
    assert np.isfinite(out).all() and (out[~live] == 0).all()
    return float(np.abs(out[live] - ref[live]).max())


@pytest.mark.parametrize("variant", ["s1", "s3", "int8", "softcap"])
@pytest.mark.parametrize("lanes", sorted(_PARITY_GEOMS))
def test_decode_kernel_bf16_operands_are_no_further_from_float32(lanes,
                                                                 variant):
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    worst = _parity_worst(paged_decode_attention_mq, lanes, variant)
    assert worst <= _PARENT_WORST[lanes, variant], worst


def _remainder_error(kernel, interpret=True):
    """Two kinds of key a row: p = 1 for the first half and one x < 1 for
    the second, V = +1 against -c over the lanes, c sweeping [1, 2): where
    x * c is near 1 the output nearly cancels, and an x rounded to bf16
    (relative error up to 2^-9, the same on every key) stands out of the
    small output's own rounding by 10 to 100 times.  Worst |kernel -
    float64| over the outputs under 2^-6."""
    h, hk, d, bs, m, b = 8, 2, 128, 32, 16, 8
    rng = np.random.default_rng(4747)
    half = np.asarray([64, 0, 200, 0, 33, 256, 0, 7])
    lens = (2 * half).astype(np.int32)
    u = rng.choice([-1.0, 1.0], size=d)
    gamma = 0.75 + np.arange(h) * 0.09375          # exact in bf16
    q = np.broadcast_to((gamma[:, None] * u)[None, None], (b, 1, h, d))
    c = 1 + np.arange(d) / 128.0                   # exact in bf16
    need = -(-lens // bs)
    n = int(need.sum()) + 1
    bt = np.zeros((b, m), np.int32)
    kv = np.zeros((1, n, 2, bs, hk * d), np.float32)
    at = 1
    for r in range(b):
        bt[r, : need[r]] = np.arange(at, at + need[r])
        first = (np.arange(need[r] * bs) < half[r])[:, None, None]
        keys = np.where(first, u, u * 0.96875) * np.ones((1, hk, 1))
        vals = np.where(first, 1.0, -c) * np.ones((1, hk, 1))
        kv[0, at: at + need[r], 0] = keys.reshape(need[r], bs, hk * d)
        kv[0, at: at + need[r], 1] = vals.reshape(need[r], bs, hk * d)
        at += need[r]
    out = np.asarray(kernel(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(kv, jnp.bfloat16),
        jnp.int32(0), jnp.asarray(bt), jnp.asarray(lens),
        jnp.asarray(np.maximum(lens - 1, 0)), interpret=interpret),
        np.float64)
    # the same arithmetic in float64: s_first - s_second = 128 / sqrt(128)
    # * gamma / 32
    x = np.exp(-(d ** 0.5) * gamma / 32)
    ref = (1 - x[:, None] * c[None, :]) / (1 + x[:, None])
    small = np.abs(ref) < 2.0 ** -6
    assert small.any(axis=1).all()                 # every head cancels somewhere
    return float(np.abs(out[lens > 0][:, 0] - ref[None])[:, small].max())


def test_decode_kernel_keeps_the_remainder_of_p():
    """Fails if p goes to the matrix unit rounded to bf16 without its
    remainder (1.4e-3 then; ~16 bits of p give under 1e-4)."""
    from dynamo_tpu.ops.pallas.decode_attention import (
        paged_decode_attention_mq,
    )

    assert _remainder_error(paged_decode_attention_mq) < 1e-4


from kernel_oracles import assert_canary_clean, interpret_cases  # noqa: E402


@pytest.mark.parametrize("case", interpret_cases(), ids=lambda c: c["name"])
def test_audit_matrix_canary_clean(case):
    """Every interpret-mode case in the registry's audit matrix passes
    the NaN-canary differential: live lanes on-oracle within the case's
    atol, finite when padding lanes and out-of-seq_len cache blocks are
    poisoned with NaN, exact-zero claims exactly zero.  This is the SAME
    matrix `dynamo-tpu lint --kern` audits (KN004) — the hand-written
    oracle tests above pin specific shapes and options; this one pins
    the shared adversarial geometries, so a kernel regression trips both
    the lint gate and tier-1."""
    canary = assert_canary_clean(case)
    assert canary["live_lanes"] > 0, case["name"]


def test_fuzz_case_deterministic_and_canary_clean():
    """fuzz_case(seed) is the nightly kern-fuzz unit: same seed, same
    geometry (the replay token IS the seed), and a healthy kernel passes
    its canary.  One fixed seed keeps this in the tier-1 budget; the
    nightly sweeps a date-derived window."""
    from dynamo_tpu.ops.pallas.registry import fuzz_case

    a, b = fuzz_case(1234), fuzz_case(1234)
    assert a["name"] == b["name"] == "fuzz[ragged-1234]"
    assert_canary_clean(a)


# ------------------------------------------- kernels per tensor-parallel shard


@pytest.mark.parametrize("phase", ["decode", "mq", "prefill", "ragged"])
def test_dispatch_under_tp_mesh_runs_kernels_per_kv_head(phase, monkeypatch):
    """--tp > 1: GSPMD cannot partition a Mosaic call, so the dispatch in
    ops/paged_attention.py runs each kernel per kv-head shard under
    shard_map when it is traced with a model-axis mesh in scope.  On four
    virtual CPU devices (kernels in interpret mode, the backend gate
    steered to "tpu") the sharded result must equal the unsharded XLA
    oracle — a wrong head split shows up as wrong numbers, not a crash."""
    import functools
    import importlib

    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.ops.pallas import decode_attention, prefill_attention
    from dynamo_tpu.ops.pallas.registry import (
        probe_decode_inputs, probe_prefill_inputs, probe_ragged_inputs,
    )

    pa = importlib.import_module("dynamo_tpu.ops.paged_attention")
    h, hk, d, bs, n, m, b = 8, 4, 32, 16, 16, 4, 2
    f32 = jnp.float32
    if phase in ("decode", "mq"):
        s_q = 1 if phase == "decode" else 3
        lens = np.asarray([s_q, m * bs - 5], np.int32)
        q, cache, layer, bt, sl, q0 = probe_decode_inputs(
            b, h, hk, d, bs, n, m, lens, dtype=f32, s_q=s_q)
        pos = q0[:, None] + jnp.arange(s_q, dtype=jnp.int32)[None]
        fn, args, cache_at = pa.paged_attention_layer, \
            [q, cache, layer, bt, sl, pos], 1
    elif phase == "prefill":
        a = probe_prefill_inputs(b, 32, h, hk, d, bs, n, m, dtype=f32)
        fn = functools.partial(pa.prefill_attention, prefix_blocks=1)
        args, cache_at = list(a), 3
    else:
        a = probe_ragged_inputs(32, 2, h, hk, d, bs, n, m, dtype=f32)
        sid = jnp.repeat(jnp.arange(2, dtype=jnp.int32), 16)[None]
        fn = functools.partial(pa.ragged_prefill_attention, prefix_blocks=1)
        args, cache_at = list(a) + [sid], 3
    ref = fn(*args)  # backend is the CPU: the XLA oracle

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mod, names in ((decode_attention, ("paged_decode_attention",
                                           "paged_decode_attention_mq")),
                       (prefill_attention, ("paged_prefill_attention",
                                            "ragged_paged_prefill_attention"))):
        for name in names:
            monkeypatch.setattr(mod, name, functools.partial(
                getattr(mod, name), interpret=True))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    heads = NamedSharding(mesh, P(None, None, "model", None))
    args[0] = jax.device_put(args[0], heads)
    if cache_at == 3:
        args[1] = jax.device_put(args[1], heads)
        args[2] = jax.device_put(args[2], heads)
    args[cache_at] = jax.device_put(args[cache_at], NamedSharding(
        mesh, P(None, None, None, None, "model")))

    def under_mesh(*a):  # what EngineCore's jit does around its impls
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            assert pa.attention_impl(
                phase, num_kv_heads=hk, block_size=bs,
                tp=pa.tp_size()) == ("pallas", "tpu, shard_map over tp=4")
            return fn(*a)

    got = jax.jit(under_mesh)(*args)
    assert got.sharding.spec == P(None, None, "model", None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-5)
