"""The sparse-attention indexer's scores of a decode step, from the keys where
they lie.

A ``full`` layer of a model with an indexer (models/glm_dsa.py) scores, for
each row's one query, every position the row can see:

    I[b, c] = (Hi·Di)^-1/2 · Σ_h w[b, h] · relu(q[b, h] · k[b, c])

over the row's keys in ``index_k`` (ops/latent_cache.py: bf16 [Lf, N, Bs, Di],
one contiguous [Bs, Di] block a block id and layer).  The XLA form gathers
every block of every row's table into a [B, M·Bs, Di] copy and reads it back
(``index_scores`` over ``index_k[fi, block_tables]``: the whole table,
whatever the contexts are).  Here the keys are fetched through the block
table, a chunk of whole blocks at a time, and scored in VMEM:

``dsa_index_scores``  one grid step is one *group* of the step's rows — the
    rows whose tables name the same leading blocks (``decode_groups`` of
    ops/pallas/mla_dense_attention.py, found once a step for every ``full``
    layer), at most ``group_rows`` of them.  The group's shared chunks —
    ``blocks_per_chunk`` whole blocks, one DMA a block, double buffered — are
    fetched once and scored against the members' stacked queries [n·Hi, Di]
    in one matrix product; relu, the members' head weights and the scale are
    applied to the product in VMEM and a member's row of the chunk's scores
    is written.  Each member then walks its own remaining blocks alone.  No
    block past ⌈len / Bs⌉ is fetched and an empty slot fetches none (the
    rule of ``mla_dense_decode``).  A score depends on its own key alone, so
    a row's scores are the same bits alone and in any group.

What the kernel never visits — the positions past a row's last owned chunk,
every position of an empty slot — holds whatever the output buffer held, and
the tail of a partly owned chunk is scored from stale VMEM: the caller masks
by what each query may see (``models/glm_dsa.py::_select``: the one place a
position's visibility is decided) before anything reads a score.

The output [B, C] f32 stays in VMEM for the whole call (a group's members sit
anywhere in the batch), so the call is sized by it: ``fits`` says whether a
step's scores do, and a larger step keeps the XLA form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.registry import (
    DSA_INDEX_BLOCKS_PER_CHUNK,
    DSA_INDEX_GROUP_ROWS,
    DSA_INDEX_VMEM_BYTES,
    dsa_index_vmem_bytes,
)

__all__ = ["dsa_index_scores", "index_keys_read", "fits", "KERNEL_NAME"]

KERNEL_NAME = "dsa_index_scores"
LANES = 128


def fits(b: int, table_blocks: int, block_size: int, heads: int, dim: int,
         blocks_per_chunk: int = DSA_INDEX_BLOCKS_PER_CHUNK,
         group_rows: int = DSA_INDEX_GROUP_ROWS) -> bool:
    """Whether a decode step of ``b`` rows over tables of ``table_blocks``
    blocks can be scored by the kernel: whole lane groups a key and a chunk,
    two of them or more a chunk (the compiler refuses the store of one row
    of one lane group at a row it is told at run time), and every row's
    scores resident in VMEM."""
    c = min(blocks_per_chunk, table_blocks)
    t = c * block_size
    return (dim % LANES == 0 and t % LANES == 0 and t > LANES
            and dsa_index_vmem_bytes(
                b, table_blocks * block_size, heads, dim, t,
                min(group_rows, b)) <= DSA_INDEX_VMEM_BYTES)


def index_keys_read(block_tables, seq_lens, block_size: int,
                    blocks_per_chunk: int = DSA_INDEX_BLOCKS_PER_CHUNK,
                    group_rows: int = DSA_INDEX_GROUP_ROWS) -> int:
    """Index-key rows the kernel fetches for one ``full`` layer of a decode
    step (host arrays): whole blocks, a group's shared ones once and each
    member's own."""
    import numpy as np

    from dynamo_tpu.ops.pallas.mla_dense_attention import decode_groups

    groups = decode_groups(np, block_tables, seq_lens, block_size,
                           blocks_per_chunk, group_rows)
    count, shared = groups[:, 0], groups[:, 1]
    owned = np.minimum(-(-seq_lens.astype(np.int64) // block_size),
                       block_tables.shape[1])
    return int((owned.sum() - (np.maximum(count, 1) - 1) @ shared)
               * block_size)


def _kernel(len_ref, bt_ref, grp_ref, q_ref, w_ref, keys_hbm, out_ref,
            qs_ref, ws_ref, buf, sems, *, c: int, scale: float):
    lead = pl.program_id(0)
    g, h, d = qs_ref.shape
    bs = buf.shape[2]
    t = c * bs
    count = grp_ref[lead, 0]
    shared = grp_ref[lead, 1] // c            # chunks every member shares

    def member(j):
        return grp_ref[lead, 2 + j]

    def score(slot, first, n):
        """f32 [n, T]: the chunk in ``buf[slot]`` scored for the stacked
        members ``first`` .. ``first + n``."""
        part = pl.ds(first, n)
        dots = jax.lax.dot_general(
            qs_ref[part].reshape(n * h, d), buf[slot].reshape(t, d),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        weighed = jnp.maximum(dots, 0.0) * ws_ref[part].reshape(
            n * h, LANES)[:, :1]
        return jnp.sum(weighed.reshape(n, h, t), axis=1) * scale

    def walk(row, first, last, update):
        """Chunks [first, last) of ``row``'s table (``last`` None: to the
        row's end) through the two buffers, chunk ci + 1 in flight while
        ``update(ci, slot)`` scores chunk ci."""
        owned = jnp.minimum(pl.cdiv(len_ref[row], bs), bt_ref.shape[1])
        last = pl.cdiv(owned, c) if last is None else last

        def block_dmas(ci, slot, wait=False):
            for i in range(c):                # static: C copies a chunk
                @pl.when(ci * c + i < owned)
                def _copy(i=i):
                    dma = pltpu.make_async_copy(
                        keys_hbm.at[bt_ref[row, ci * c + i]],
                        buf.at[slot, i], sems.at[slot, i])
                    if wait:
                        dma.wait()
                    else:
                        dma.start()

        @pl.when(first < last)
        def _first():
            block_dmas(first, 0)

        def body(ci, _):
            slot = jax.lax.rem(ci - first, 2)

            @pl.when(ci + 1 < last)
            def _prefetch():
                block_dmas(ci + 1, 1 - slot)

            block_dmas(ci, slot, wait=True)
            update(ci, slot)
            return 0

        jax.lax.fori_loop(first, last, body, 0)

    def write(j, ci, row):
        out_ref[pl.ds(member(j), 1), pl.ds(pl.multiple_of(ci * t, t), t)] = row

    @pl.when(count > 0)
    def _group():
        for j in range(g):

            @pl.when(j < count)
            def _query(j=j):
                qs_ref[j] = q_ref[member(j)]
                ws_ref[j] = w_ref[member(j)]

        # the shared chunks, once for all members: their queries stacked
        # over each key tile, in the next power of two of rows (what a dead
        # row of the stack scores is written nowhere)
        lo = 1
        while lo < g:
            k = min(2 * lo, g)

            @pl.when((count > lo) & (count <= k))
            def _shared(k=k):
                def update(ci, slot):
                    scores = score(slot, 0, k)
                    for j in range(k):

                        @pl.when(j < count)
                        def _row(j=j):
                            write(j, ci, scores[j:j + 1])

                walk(member(0), 0, shared, update)

            lo = k

        def own(j, _):
            walk(member(j), shared, None,
                 lambda ci, slot: write(j, ci, score(slot, j, 1)))
            return 0

        jax.lax.fori_loop(0, count, own, 0)


@functools.partial(jax.jit, static_argnames=("blocks_per_chunk", "group_rows",
                                             "interpret"))
def dsa_index_scores(
    q: jax.Array,             # [B, Hi, Di] the rows' indexer queries
    w: jax.Array,             # [B, Hi] the rows' head weights
    keys: jax.Array,          # [R, Bs, Di] every full layer's key blocks, flat
    block_tables: jax.Array,  # [B, M] int32 rows of ``keys``
    seq_lens: jax.Array,      # [B] int32 positions each query sees (0: none)
    groups: jax.Array | None = None,   # [B, 2 + G]: ``decode_groups``
    *, blocks_per_chunk: int = DSA_INDEX_BLOCKS_PER_CHUNK,
    group_rows: int = DSA_INDEX_GROUP_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """f32 [B, M·Bs]: row b's index scores at the positions of the chunks it
    owns (those under ⌈len / (C·Bs)⌉·C·Bs); anything elsewhere.  ``groups``
    of the step's tables at this chunk size (any layer's: a block id's offset
    moves no equality); None works them out here."""
    from dynamo_tpu.ops.pallas.mla_dense_attention import decode_groups

    b, h, d = q.shape
    _, bs, _ = keys.shape
    m = block_tables.shape[1]
    c = min(blocks_per_chunk, m)
    if groups is None:
        groups = decode_groups(jnp, block_tables, seq_lens, bs, c, group_rows)
    g = groups.shape[1] - 2
    # whole chunks of positions: the last chunk of a table that is not whole
    # chunks is scored into columns the caller cuts off
    cols = -(-m // c) * c * bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            # every row's query, weights and scores stay in VMEM: a group's
            # members sit anywhere in the batch
            pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((b, h, LANES), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # keys stay in HBM
        ],
        out_specs=pl.BlockSpec((b, cols), lambda i, *_: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, h, d), keys.dtype),
            pltpu.VMEM((g, h, LANES), jnp.float32),
            pltpu.VMEM((2, c, bs, d), keys.dtype),
            pltpu.SemaphoreType.DMA((2, c)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, c=c, scale=float((h * d) ** -0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=DSA_INDEX_VMEM_BYTES),
        interpret=interpret,
        name=KERNEL_NAME,
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      groups.astype(jnp.int32), q.astype(keys.dtype),
      jnp.broadcast_to(w.astype(jnp.float32)[:, :, None], (b, h, LANES)),
      keys)
    return out[:, :m * bs]
