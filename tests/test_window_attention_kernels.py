"""The windowed forms of the paged-attention kernels against the XLA forms
(interpret mode on the CPU: a block the kernel did not copy is NaN there, so
a walk that begins one block late, or a mask one position off, shows).

The XLA forms (ops/paged_attention.py: ``paged_attention``,
``prefill_attention``, ``ragged_prefill_attention``) mask by position,
``0 <= p - j < window``, over the whole gathered table: they are the oracle.
The last tests pin what the other configurations rest on: with
``window=None`` each kernel lowers to the text it lowered to before it knew
of a window.
"""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.pallas.decode_attention import (
    paged_decode_attention,
    paged_decode_attention_mq,
)
from dynamo_tpu.ops.pallas.prefill_attention import (
    paged_prefill_attention,
    ragged_paged_prefill_attention,
)

pa = importlib.import_module("dynamo_tpu.ops.paged_attention")

H, HK, D = 4, 2, 32


def _cache(rng, n, bs, layers=2):
    return jnp.asarray(rng.normal(size=(layers, n, 2, bs, HK * D)),
                       jnp.float32)


def _tables(rng, b, m, n):
    """Disjoint tables: a row never owns another row's block."""
    assert b * m <= n
    return jnp.asarray(rng.permutation(n)[: b * m].reshape(b, m), jnp.int32)


def _poison_unowned(cache, bt, lens, bs, layer):
    """NaN in every block of the layer no row owns and past every row's
    length inside its last block: a fetch or an unmasked read shows."""
    c = np.array(cache)
    owned = np.zeros(c.shape[1], bool)
    for row, n in zip(np.asarray(bt), np.asarray(lens)):
        blocks = -(-int(n) // bs)
        owned[row[:blocks]] = True
        if n % bs and blocks:
            c[layer, row[blocks - 1], :, int(n) % bs:] = np.nan
    c[layer, ~owned] = np.nan
    return jnp.asarray(c)


def _xla_decode(q, cache, layer, bt, lens, q0, window):
    _, n, _, bs, _ = cache.shape
    b, s = q.shape[:2]
    kc = cache[layer, :, 0].reshape(n, bs, HK, D)
    vc = cache[layer, :, 1].reshape(n, bs, HK, D)
    pos = q0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    return pa.paged_attention(q, kc, vc, bt, lens, pos, window=window)


# lengths against the window: under it, at it, one past it, far past it,
# an empty slot, a single token; mixed in one group on purpose
_LENS = {
    16: [5, 16, 17, 90, 0, 1, 48, 33],
    24: [24, 25, 7, 128, 0, 100, 49, 23],      # W not a multiple of Bs 16
    40: [40, 41, 39, 120, 64, 0, 1, 81],
}


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("window", sorted(_LENS))
def test_decode_kernel_walks_the_window_only(window, bs):
    rng = np.random.default_rng(window * 31 + bs)
    lens = np.asarray(_LENS[window], np.int32)
    b, m = len(lens), 128 // bs
    n = b * m
    bt = _tables(rng, b, m, n)
    cache = _cache(rng, n, bs)
    layer = 1
    q = jnp.asarray(rng.normal(size=(b, 1, H, D)), jnp.float32)
    seq = jnp.asarray(lens)
    ref = _xla_decode(q, cache, layer, bt, seq, seq - 1, window)
    # everything before a row's first block of the band is NaN too: the
    # kernel may not fetch it
    c = np.array(_poison_unowned(cache, bt, lens, bs, layer))
    for row, n_ in zip(np.asarray(bt), lens):
        first = max(int(n_) - window, 0) // bs
        c[layer, row[:first]] = np.nan
    out = paged_decode_attention(
        q[:, 0], jnp.asarray(c), jnp.int32(layer), bt, seq,
        blocks_per_chunk=2, seqs_per_group=4, window=window, interpret=True)
    live = lens > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live, 0], atol=2e-5)
    assert not np.asarray(out)[~live].any()     # an empty slot gives 0


@pytest.mark.parametrize("window,bs", [(16, 8), (24, 16)])
def test_mq_decode_kernel_masks_each_query_by_its_own_band(window, bs):
    rng = np.random.default_rng(5)
    s = 4
    lens = np.asarray([s, 19, 40, 77], np.int32)     # context incl. queries
    b, m = len(lens), 96 // bs
    bt = _tables(rng, b, m, b * m)
    cache = _cache(rng, b * m, bs)
    q = jnp.asarray(rng.normal(size=(b, s, H, D)), jnp.float32)
    seq = jnp.asarray(lens)
    q0 = seq - s
    ref = _xla_decode(q, cache, 0, bt, seq, q0, window)
    out = paged_decode_attention_mq(
        q, _poison_unowned(cache, bt, lens, bs, 0), jnp.int32(0), bt, seq,
        q0, blocks_per_chunk=2, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_int8_decode_kernel_takes_the_window_too():
    from dynamo_tpu.ops.pallas.registry import quantize_audit_cache

    rng = np.random.default_rng(9)
    bs, window = 32, 40
    lens = np.asarray([100, 33, 0, 64], np.int32)
    b, m = len(lens), 4
    bt = _tables(rng, b, m, b * m)
    qcache = quantize_audit_cache(_cache(rng, b * m, bs, layers=1), HK)
    q = jnp.asarray(rng.normal(size=(b, 1, H, D)), jnp.float32)
    seq = jnp.asarray(lens)
    full = paged_decode_attention(q[:, 0], qcache, jnp.int32(0), bt, seq,
                                  interpret=True)
    out = paged_decode_attention(q[:, 0], qcache, jnp.int32(0), bt, seq,
                                 window=window, interpret=True)
    # rows inside the window read what the full kernel reads; past it not
    np.testing.assert_allclose(np.asarray(out)[1], np.asarray(full)[1],
                               atol=1e-6)
    assert np.abs(np.asarray(out)[0] - np.asarray(full)[0]).max() > 1e-3
    from dynamo_tpu.ops.kv_quant import dequant_layer_slice

    plain = dequant_layer_slice(qcache.data[0], qcache.scale[0], HK)[None]
    ref = _xla_decode(q, plain.astype(jnp.float32), 0, bt, seq, seq - 1,
                      window)
    live = lens > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live, 0], atol=2e-4)


# ------------------------------------------------------------- prefill
def _xla(monkeypatch):
    monkeypatch.setenv("DYNAMO_DISABLE_PALLAS_PREFILL", "1")


@pytest.mark.parametrize("window,bs,prefix_blocks,s,tq,c", [
    (16, 8, 0, 64, 16, 2),     # no prefix: the band's edge inside the chunk
    (16, 8, 6, 32, 16, 2),     # W < S: later tiles read no prefix at all
    (24, 16, 5, 32, 16, 2),    # W not a multiple of Bs; edge inside a block
    (48, 16, 7, 32, 32, 4),    # W > S: every tile reads prefix and chunk
    (200, 8, 4, 32, 16, 2),    # the window holds everything: full attention
])
def test_prefill_kernel_streams_and_masks_the_band(
        monkeypatch, window, bs, prefix_blocks, s, tq, c):
    rng = np.random.default_rng(window + s)
    b, m = 2, prefix_blocks + s // bs + 1
    bt = _tables(rng, b, m, b * m)
    cache = _cache(rng, b * m, bs)
    start = jnp.asarray([prefix_blocks * bs] * b, jnp.int32)
    fresh = np.asarray([s, s - 5], np.int32)          # a padded tail row
    seq = start + jnp.asarray(fresh)
    q = jnp.asarray(rng.normal(size=(b, s, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, HK, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, HK, D)), jnp.float32)
    _xla(monkeypatch)
    ref = pa.prefill_attention(q, k, v, cache, jnp.int32(1), bt, seq, start,
                               prefix_blocks, window=window)
    # prefix blocks wholly before the first query's band may not be read
    poisoned = np.array(cache)
    dead = max(prefix_blocks * bs - (window - 1), 0) // bs
    for row in np.asarray(bt):
        poisoned[1, row[:dead]] = np.nan
    out = paged_prefill_attention(
        q, k, v, jnp.asarray(poisoned), jnp.int32(1), bt, seq, start,
        rows_per_chunk=tq, blocks_per_chunk=c, window=window, interpret=True)
    for i in range(b):
        np.testing.assert_allclose(np.asarray(out)[i, :fresh[i]],
                                   np.asarray(ref)[i, :fresh[i]], atol=3e-5)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("window,bs", [(16, 8), (24, 16), (40, 8)])
def test_ragged_prefill_kernel_streams_and_masks_the_band(
        monkeypatch, window, bs):
    """Three spans on one flat axis (a chunk deep in a prompt, a fresh
    prompt, a chunk whose prefix is shorter than the window) and padding."""
    rng = np.random.default_rng(window)
    takes = [32, 16, 24 if bs == 8 else 16]
    prefix = [6, 0, 1]                                  # cached blocks a row
    t = 96
    r, m = len(takes), 12
    bt = _tables(rng, r, m, r * m)
    cache = _cache(rng, r * m, bs)
    starts = jnp.asarray([p * bs for p in prefix], jnp.int32)
    offs = np.concatenate([[0], np.cumsum(takes)[:-1]]).astype(np.int32)
    seq_ids = np.full((1, t), -1, np.int32)
    for i, (o, n) in enumerate(zip(offs, takes)):
        seq_ids[0, o:o + n] = i
    seq = starts + jnp.asarray(takes, jnp.int32)
    q = jnp.asarray(rng.normal(size=(1, t, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, HK, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, t, HK, D)), jnp.float32)
    _xla(monkeypatch)
    ref = pa.ragged_prefill_attention(
        q, k, v, cache, jnp.int32(0), bt, seq, starts, jnp.asarray(offs),
        jnp.asarray(seq_ids), max(prefix), window=window)
    poisoned = np.array(cache)
    for row, p in zip(np.asarray(bt), prefix):
        poisoned[0, row[:max(p * bs - (window - 1), 0) // bs]] = np.nan
    out = ragged_paged_prefill_attention(
        q, k, v, jnp.asarray(poisoned), jnp.int32(0), bt, seq, starts,
        jnp.asarray(offs), rows_per_chunk=16, blocks_per_chunk=2,
        window=window, interpret=True)
    real = seq_ids[0] >= 0
    np.testing.assert_allclose(np.asarray(out)[0, real],
                               np.asarray(ref)[0, real], atol=3e-5)
    assert np.isfinite(np.asarray(out)).all()


# -------------------------------------------- window=None is the old kernel
# sha256 of each kernel's lowered text at the parent commit (PR 59) for the
# shapes below: ``PYTHONPATH=. python tests/test_window_attention_kernels.py``
# prints new ones after a change meant to alter the full-attention kernels.
_PARENT = {
    "decode": "315f11ce8bb7ef81",
    "mq": "23670a4a72a9d754",
    "prefill": "f86e6463cbdef833",
    "ragged": "068735502422ca25",
}


def _lowered(which: str, **kw) -> str:
    bs, m, n = 16, 8, 32
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    cache = sds((2, n, 2, bs, HK * D), f32)
    if which in ("decode", "mq"):
        s = 1 if which == "decode" else 4
        args = (sds((8, s, H, D), f32), cache, sds((), i32),
                sds((8, m), i32), sds((8,), i32), sds((8,), i32))
        fn = paged_decode_attention_mq
    elif which == "prefill":
        args = (sds((1, 64, H, D), f32), sds((1, 64, HK, D), f32),
                sds((1, 64, HK, D), f32), cache, sds((), i32),
                sds((1, m), i32), sds((1,), i32), sds((1,), i32))
        fn = paged_prefill_attention
    else:
        args = (sds((1, 64, H, D), f32), sds((1, 64, HK, D), f32),
                sds((1, 64, HK, D), f32), cache, sds((), i32),
                sds((3, m), i32), sds((3,), i32), sds((3,), i32),
                sds((3,), i32))
        fn = ragged_paged_prefill_attention
    return fn.lower(*args, interpret=True, **kw).as_text()


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("which", sorted(_PARENT))
def test_without_a_window_a_kernel_lowers_to_the_parents_text(which):
    assert _digest(_lowered(which)) == _PARENT[which]
    assert _digest(_lowered(which, window=24)) != _PARENT[which]


@pytest.mark.parametrize("phase", ["decode", "mq", "prefill", "ragged"])
def test_the_dispatch_names_the_window_only_where_the_table_can_pass_it(
        monkeypatch, phase):
    """On the TPU a window is a Pallas call of the ``*_window*`` name where
    the block table can hold a context past it, and the full kernel's call
    where it cannot (full attention is exact there)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.attention_impl(phase, num_kv_heads=HK, block_size=16,
                             windowed=True)[0] == "pallas"
    bs, n = 16, 32
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    cache = sds((1, n, 2, bs, HK * D), f32)

    def names(m, window):
        if phase in ("decode", "mq"):
            s = 1 if phase == "decode" else 4
            fn = lambda *a: pa.paged_attention_layer(*a, window=window)
            args = (sds((8, s, H, D), f32), cache, sds((), i32),
                    sds((8, m), i32), sds((8,), i32), sds((8, s), i32))
        elif phase == "prefill":
            fn = lambda *a: pa.prefill_attention(*a, 0, window=window)
            args = (sds((1, 64, H, D), f32), sds((1, 64, HK, D), f32),
                    sds((1, 64, HK, D), f32), cache, sds((), i32),
                    sds((1, m), i32), sds((1,), i32), sds((1,), i32))
        else:
            fn = lambda *a: pa.ragged_prefill_attention(*a, 0, window=window)
            args = (sds((1, 64, H, D), f32), sds((1, 64, HK, D), f32),
                    sds((1, 64, HK, D), f32), cache, sds((), i32),
                    sds((3, m), i32), sds((3,), i32), sds((3,), i32),
                    sds((3,), i32), sds((1, 64), i32))
        return str(jax.make_jaxpr(fn)(*args))

    stem = ("paged_decode_attention" if phase in ("decode", "mq")
            else "paged_prefill_attention")
    assert f"{stem}_window" in names(8, 64)       # 128 tokens of table > 64
    assert f"{stem}_window" not in names(4, 64)   # 64 tokens: full is exact
    assert stem in names(4, 64)
    assert f"{stem}_window" not in names(8, None)


if __name__ == "__main__":
    for which in sorted(_PARENT):
        print(f'    "{which}": "{_digest(_lowered(which))}",')
