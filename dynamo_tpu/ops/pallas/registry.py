"""Kernel registry — the single source of truth for Pallas kernel geometry.

Every `pallas_call` site in ops/pallas/ is registered here together with
the geometry matrix it is audited under (analysis/kerncheck.py, the
dtkern plane) and probed under (benchmarks/probe_kernels.py, chip_smoke.py).
The registry owns three things the kernels themselves must not:

- **tile constants**: blocks-per-chunk / rows-per-chunk / matmul block
  sizes.  The kernels import their defaults from here, so a tuning-knob
  change is one edit that the audit, the probes and the serving path all
  see (DT105 flags integer tile literals that bypass this table).
- **the audit matrix**: per-kernel geometry cases, including the
  adversarial ragged shapes (empty rows, 1-token decode rows,
  non-block-divisible lengths, max-block rows, non-block-aligned decode
  starts) that the NaN-canary padding oracles run against, plus
  serving-scale spec-only cases that are shape-traced (jax.eval_shape)
  for VMEM/pricing without executing.
- **capture + pricing**: a `pallas_call` spy that records grid, specs,
  scratch and operands at call time, and the analytic cost model (HBM
  DMA bytes / FLOPs / transcendentals) shared between the kern-manifest
  pricing facts and the `cost_estimate=` each attention kernel hands
  XLA's scheduler.

kerncheck turns the captures into KN001-KN006 facts; this module stays
importable from ops/ (no analysis imports) and imports the kernels only
lazily inside builders so the kernels can import the constants above.
"""

from __future__ import annotations

import contextlib
import functools
import math

__all__ = [
    "DECODE_BLOCKS_PER_CHUNK",
    "DECODE_SEQS_PER_GROUP",
    "DECODE_CHUNK_BYTES",
    "DECODE_MAX_BLOCKS_PER_CHUNK",
    "DECODE_MAX_DMA_SITES",
    "DECODE_SEQS_PER_UPDATE",
    "DECODE_KEYS_PER_UPDATE_SEQ",
    "PREFILL_ROWS_PER_CHUNK",
    "PREFILL_BLOCKS_PER_CHUNK",
    "PREFILL_QUERY_ROWS",
    "prefill_rows_per_chunk",
    "INT8_MATMUL_BM",
    "INT8_MATMUL_BN",
    "INT8_MATMUL_BK",
    "MLA_SPARSE_ROWS_PER_TILE",
    "MLA_SPARSE_LIST_ALIGN",
    "MLA_MASKED_TOKENS_PER_TILE",
    "MLA_MASKED_KEYS_PER_TILE",
    "MLA_MASKED_TILE_NS",
    "MLA_SPARSE_ROW_NS",
    "DSA_INDEX_BLOCKS_PER_CHUNK",
    "DSA_INDEX_GROUP_ROWS",
    "DSA_INDEX_VMEM_BYTES",
    "dsa_index_vmem_bytes",
    "dsa_index_cost",
    "LINEAR_STATE_HEADS_PER_STEP",
    "linear_state_heads_per_step",
    "SSM_STATE_BLOCK_BYTES",
    "ssm_state_heads_per_step",
    "SELECTIVE_SCAN_TOKENS",
    "SELECTIVE_SCAN_ROWS_PER_TILE",
    "SELECTIVE_SCAN_SCALARS",
    "GROUPED_MATMUL_ROW_TILE",
    "GROUPED_MATMUL_ROW_TILE_BYTES",
    "GROUPED_MATMUL_COMPILER_VMEM_BYTES",
    "GROUPED_MATMUL_BLOCK_BYTES",
    "GROUPED_MATMUL_MAX_ROWS_PER_GROUP",
    "grouped_matmul_row_tile",
    "grouped_matmul_tiling",
    "grouped_matmul_pairs",
    "grouped_matmul_vmem_bytes",
    "V5E_VMEM_BYTES",
    "VMEM_BUDGET_BYTES",
    "SCOPED_VMEM_BYTES",
    "decode_vmem_bytes",
    "decode_tiling",
    "decode_group_and_chunk",
    "decode_seqs_per_update",
    "KERNELS",
    "audit_cases",
    "fuzz_case",
    "capture_pallas_calls",
    "decode_kernel_cost",
    "prefill_kernel_cost",
    "ragged_kernel_cost",
    "int8_matmul_cost",
    "mla_sparse_cost",
    "mla_masked_cost",
    "masked_prefill_is_cheaper",
    "latent_dma_cost",
    "linear_state_cost",
    "linear_state_reference",
    "ssm_state_cost",
    "ssm_state_reference",
    "selective_step_cost",
    "selective_scan_cost",
    "selective_step_reference",
    "selective_scan_reference",
    "grouped_matmul_cost",
    "grouped_matmul_reference",
    "decode_cost_estimate",
    "prefill_cost_estimate",
    "ragged_cost_estimate",
    "fallback_census",
    "probe_coverage",
    "quantize_audit_cache",
]

# ------------------------------------------------------- tile constants ----
# The serving tile sizes.  decode: 8 sequences per grid step, and a
# row-chunk of ~512 KiB of K/V whatever the row's width (``decode_tiling``:
# 4 blocks of 32 tokens at 1,024 bf16 lanes, 16 at 256, never more than 16
# blocks, nor more than 128 unrolled DMA sites a chunk: the group gives
# way); 4 blocks is what the cost model prices when it is not told.
# prefill: 128 query rows per grid step keeps acc/m/l scratch + the
# VMEM-resident fresh K/V well inside VMEM at S=2048.  int8_matmul:
# MXU-shaped (128, 512, 512).
DECODE_BLOCKS_PER_CHUNK = 4
DECODE_SEQS_PER_GROUP = 8
DECODE_CHUNK_BYTES = 512 * 1024
DECODE_MAX_BLOCKS_PER_CHUNK = 16
DECODE_MAX_DMA_SITES = 128
# sequences one flash update takes at once (``decode_seqs_per_update``),
# and the keys of a chunk each of them asks for
DECODE_SEQS_PER_UPDATE = 4
DECODE_KEYS_PER_UPDATE_SEQ = 32
PREFILL_ROWS_PER_CHUNK = 128
PREFILL_BLOCKS_PER_CHUNK = 8
# query rows (tokens x heads) a grid step of the prefill kernel holds: its
# float32 accumulator and running max / sum are 3 x rows x 128 lanes, 6 MB at
# 32 heads x 128 tokens, what the kernel was sized for
PREFILL_QUERY_ROWS = 4096
INT8_MATMUL_BM = 128
INT8_MATMUL_BN = 512
INT8_MATMUL_BK = 512
# sparse latent attention: cache rows gathered per double-buffered tile
# (one DMA a row; 256 rows x 1536 B = 384 KiB a buffer)
MLA_SPARSE_ROWS_PER_TILE = 256
# ... and the multiple a query's row list is padded to (the tiling of a
# flat int32 array, out of which the kernel slices one list)
MLA_SPARSE_LIST_ALIGN = 1024
# masked latent prefill: 16 tokens x 64 heads = 1,024 query rows meet 512
# keys a grid step (scores 2 MiB, accumulator 2 MiB in f32)
MLA_MASKED_TOKENS_PER_TILE = 16
MLA_MASKED_KEYS_PER_TILE = 512
# what the two forms of a prefill chunk's attention over a selection cost on
# one v5e chip, for ``masked_prefill_is_cheaper`` (benchmarks/probe_kernels.py
# question, PR 65): a live grid step of the masked kernel at the tile above is
# 6.95 us where every step is live (88% of the bf16 peak) and 7.28 us where
# dead steps are skipped between them (160 tokens over 24,700 rows of a
# (256, 33,280) shape: 3.57 ms); a row the gather fetches is 26.4 ns (one DMA
# a row, bound by issuing them: 160 x 2,048 rows in 8.67 ms, 50 x 2,048 in
# 2.70).  Beside its kernel the masked form copies and unpacks the context
# and builds the bias, 0.58 ms at 33 k positions: ~8% of the kernel there
MLA_MASKED_TILE_NS = 7300
MLA_SPARSE_ROW_NS = 26
# the indexer's decode scores (ops/pallas/dsa_index_scores.py): blocks of
# index keys fetched and scored together (32 x 32 = 1,024 keys, 256 KiB a
# buffer at 128 bf16 lanes: a block is an 8 KB DMA, and the kernel is bound by
# issuing them), the rows that may share one fetch of a document's keys, and
# the VMEM the call may take: every row's scores stay resident (32 rows x
# 36,864 positions in f32 are 4.5 MiB, twice for the pipeline's two buffers),
# which is past the compiler's default of 16 MiB with the buffers beside them
DSA_INDEX_BLOCKS_PER_CHUNK = 32
DSA_INDEX_GROUP_ROWS = 8
DSA_INDEX_VMEM_BYTES = 48 * 1024 * 1024
# recurrent state update: heads of one slot a grid step.  16 matrices of
# 128 x 128 float32 are 1 MiB a buffer, 4 MiB double buffered in and out,
# and their 48 q | k | g vectors fit the one 128-row tile a step transposes
LINEAR_STATE_HEADS_PER_STEP = 16
# state-space state update: the bytes of one slot's matrices a grid step, as
# many heads as make them (32 of 64 x 128 float32: 16 rows of x at two heads
# a 128-lane row, turned into columns in one tile)
SSM_STATE_BLOCK_BYTES = 1024 * 1024
# selective scan of a prefill chunk: the tokens a grid step walks with a
# channel tile's state in registers (their x, Δ and y blocks are 512 KiB
# each at 8 rows of 128 lanes), the register rows a channel tile (8: a
# tile's N = 16 state registers, its 16 rows of A and a token's handful fit
# the 64 the core has), and the numbers of B and of C one call reads from
# SMEM (512 tokens x 16: 64 KiB each; a longer chunk goes in pieces)
SELECTIVE_SCAN_TOKENS = 128
SELECTIVE_SCAN_ROWS_PER_TILE = 8
SELECTIVE_SCAN_SCALARS = 8192
# the experts' grouped matmul: sorted rows a row tile (a pair's matmul takes
# the whole tile, so the tile is what the matrix unit streams past each
# weight it loads) and the bytes it may hold (at 6,144 columns 64 rows), the
# bytes of one weight block (the whole K and a slice of N:
# ``grouped_matmul_tiling``), what the compiler keeps beside the buffers a
# kernel declares (0.8 MB read on the chip), and the rows an expert up to
# which the kernel is taken (``grouped_matmul.py::grouped_matmul_impl``):
# one row tile.  Up to there a group meets each of its weight blocks once
# or twice and the block's DMA hides the matmul; beyond, a group spans
# several tiles, the matrix unit sets the pace, and XLA's ``ragged-dot``
# closes in: on the chip the kernel won by 2.1-3.0x at 32 rows, 2.0-2.9x at
# 64, 1.6-2.7x at 128 and 1.2-2.0x at 256 (GLM's 25.2 MB and Qwen3's 3.1 MB
# experts), and lost at 512 at GLM's (the sweep: PERF.md section 6, PR 50)
GROUPED_MATMUL_ROW_TILE = 128
GROUPED_MATMUL_ROW_TILE_BYTES = 1024 * 1024
GROUPED_MATMUL_BLOCK_BYTES = 5632 * 1024
GROUPED_MATMUL_COMPILER_VMEM_BYTES = 1024 * 1024
GROUPED_MATMUL_MAX_ROWS_PER_GROUP = GROUPED_MATMUL_ROW_TILE

# v5e VMEM is 128 MiB per core (accelerator guide); budget 75% of it —
# the compiler needs headroom for spills and the double-buffer pipeline.
V5E_VMEM_BYTES = 128 * 1024 * 1024
VMEM_BUDGET_BYTES = int(V5E_VMEM_BYTES * 0.75)

# What one kernel may allocate unless it asks for more: the TPU compiler's
# scoped-VMEM limit on the v5e.  A decode kernel whose scratch passes it is
# refused at compile time ("exceeded scoped vmem limit"), and the dispatch
# would then have to drop to the XLA path.
SCOPED_VMEM_BYTES = 16 * 1024 * 1024

# Pallas allocates two buffers per blocked operand (pipeline double
# buffering); manual kvbuf scratch already carries its own factor 2.
DOUBLE_BUFFER = 2

# ------------------------------------------------------- kernel census ----
# Every pallas_call site, plus the unified-kernel placeholder: ROADMAP
# item 2 (Ragged Paged Attention, arxiv 2604.15464) replaces the
# decode/ragged-prefill split with ONE kernel.  While `unified` has no
# module, kerncheck's census reports the two-kernel split (KN006) — the
# accepted manifest entry that landing item 2 re-trips.
KERNELS = {
    "paged_decode_attention_mq": {
        "module": "dynamo_tpu.ops.pallas.decode_attention",
        "placeholder": False,
    },
    "paged_prefill_attention": {
        "module": "dynamo_tpu.ops.pallas.prefill_attention",
        "placeholder": False,
    },
    "ragged_paged_prefill_attention": {
        "module": "dynamo_tpu.ops.pallas.prefill_attention",
        "placeholder": False,
    },
    "int8_matmul": {
        "module": "dynamo_tpu.ops.pallas.int8_matmul",
        "placeholder": False,
    },
    # sparse latent attention (one kernel; a profile shows it as
    # mla_sparse_decode / mla_sparse_prefill) and the DMA-only movers
    # that keep the latent cache in the layout it needs
    "mla_sparse_attention": {
        "module": "dynamo_tpu.ops.pallas.mla_sparse_attention",
        "placeholder": False,
    },
    "latent_cache_dma": {
        "module": "dynamo_tpu.ops.pallas.latent_cache_dma",
        "placeholder": False,
    },
    "mla_sparse_prefill_masked": {
        "module": "dynamo_tpu.ops.pallas.mla_masked_prefill",
        "placeholder": False,
    },
    # the indexer's scores of a decode step, from the keys where they lie
    "dsa_index_scores": {
        "module": "dynamo_tpu.ops.pallas.dsa_index_scores",
        "placeholder": False,
    },
    # the recurrent state's decode step, one read and one write a matrix
    "linear_state_update": {
        "module": "dynamo_tpu.ops.pallas.linear_state",
        "placeholder": False,
    },
    # the state-space state's decode step, the same
    "ssm_state_update": {
        "module": "dynamo_tpu.ops.pallas.ssm_state",
        "placeholder": False,
    },
    # the selective state's decode step, the same, and the scan of a prefill
    # chunk with a channel tile's state in registers
    "selective_state_update": {
        "module": "dynamo_tpu.ops.pallas.selective_state",
        "placeholder": False,
    },
    "selective_state_scan": {
        "module": "dynamo_tpu.ops.pallas.selective_state",
        "placeholder": False,
    },
    # the experts' grouped matmul where an expert has few rows: a stream of
    # the touched experts' weights
    "grouped_expert_matmul": {
        "module": "dynamo_tpu.ops.pallas.grouped_matmul",
        "placeholder": False,
    },
    "unified_ragged_attention": {
        "module": None,  # ROADMAP item 2 — not yet written
        "placeholder": True,
    },
}


# ------------------------------------------------------------- capture ----


@contextlib.contextmanager
def capture_pallas_calls(records: list):
    """Monkeypatch `pl.pallas_call` on the shared pallas module with a
    spy that records (kernel name, grid, specs, scratch, operand avals)
    at call time and delegates to the real pallas_call.  The kernel
    modules all hold the module object (`from jax.experimental import
    pallas as pl`), so the attribute patch is visible to every site."""
    import jax.experimental.pallas as plmod

    real = plmod.pallas_call

    def spy(kernel, **kw):
        inner = real(kernel, **kw)

        def wrapped(*operands):
            records.append(_record_call(kernel, kw, operands))
            return inner(*operands)

        return wrapped

    plmod.pallas_call = spy
    try:
        yield records
    finally:
        plmod.pallas_call = real


def _kernel_name(kernel) -> str:
    fn = getattr(kernel, "func", kernel)  # unwrap functools.partial
    return getattr(fn, "__name__", repr(fn))


def _record_call(kernel, kw: dict, operands) -> dict:
    """Normalize one pallas_call into a plain capture record.  Works for
    both concrete operands (eager interpret runs) and tracers (spec-only
    jax.eval_shape runs) — only shape/dtype are read off the operands."""
    gs = kw.get("grid_spec")
    if gs is not None:
        grid = tuple(gs.grid)
        in_specs = list(gs.in_specs)
        out_specs = gs.out_specs
        scratch = list(getattr(gs, "scratch_shapes", ()) or ())
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
    else:
        grid = kw.get("grid", ())
        grid = (grid,) if isinstance(grid, int) else tuple(grid or ())
        in_specs = list(kw.get("in_specs", ()) or ())
        out_specs = kw.get("out_specs")
        scratch = list(kw.get("scratch_shapes", ()) or ())
        nsp = 0
    out_specs = (
        list(out_specs) if isinstance(out_specs, (list, tuple))
        else [out_specs]
    )
    out_shape = kw.get("out_shape")
    out_shapes = (
        list(out_shape) if isinstance(out_shape, (list, tuple))
        else [out_shape]
    )
    return {
        "name": _kernel_name(kernel),
        "grid": grid,
        "num_scalar_prefetch": nsp,
        "in_specs": in_specs,
        "out_specs": out_specs,
        "scratch": scratch,
        "operands": [(tuple(o.shape), str(o.dtype)) for o in operands],
        "out_shapes": [(tuple(o.shape), str(o.dtype)) for o in out_shapes],
        "interpret": bool(kw.get("interpret", False)),
    }


# ------------------------------------------------------- pricing model ----


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def decode_vmem_bytes(g: int, c: int, rows: int, hkd: int, bs: int,
                      cache_bytes: int = 2, q_bytes: int = 2) -> int:
    """VMEM one grid step of the flash-decode kernel holds at G sequences
    a group and C blocks a chunk: the double-buffered K/V scratch
    ``(2, G, C, 2, Bs, Hk*D)`` (all of it reserved, though a row's copies
    fill only the blocks it owns), the f32 accumulator and the m / l
    statistics, the pipelined q and output blocks (both in the query's
    dtype), and one chunk's keys and values of the sequences of one update
    as the two matmuls take them (the cache's own dtype; an int8 block
    widened to the query's)."""
    kvbuf = 2 * g * c * 2 * bs * hkd * cache_bytes
    acc_ml = g * rows * (hkd + 2 * 128) * 4
    io = DOUBLE_BUFFER * g * rows * hkd * 2 * q_bytes
    operands = (decode_seqs_per_update(g, c, bs)
                * 2 * c * bs * hkd * max(cache_bytes, q_bytes))
    return kvbuf + acc_ml + io + operands


def decode_tiling(rows: int, hkd: int, bs: int, cache_bytes: int = 2,
                  q_bytes: int = 2) -> tuple[int, int]:
    """(seqs_per_group, blocks_per_chunk) of the flash-decode kernel for a
    geometry.  A row-chunk holds ``DECODE_CHUNK_BYTES`` of K/V: what a
    row-chunk costs beside its DMA (two matmuls' latencies, max, exp, sum,
    the m / l / accumulator read-modify-write, a branch) is paid once a
    chunk, so a chunk is sized by its bytes and not by its blocks - 4
    blocks of 32 tokens at 1,024 bf16 lanes, 8 at 512, 16 at 256, 2 at
    2,048.  The unrolled DMA sites of a chunk (G x C, twice that for an
    int8 cache, whose blocks bring a scale tile each; every site has a
    semaphore in each buffer, of which a kernel may hold ~500) stay within
    ``DECODE_MAX_DMA_SITES``, the group giving way, and the scratch within
    the scoped VMEM of one kernel: halved until it fits - the blocks of a
    chunk first (the matmuls of a row's last chunk, which take the whole
    chunk, waste less of a shorter one), then the group.  What is fetched
    does not depend on either: a row copies its own ceil(len / Bs) blocks.
    ``rows`` = query rows a sequence (heads, one query each), ``hkd`` =
    Hk*D lanes of a cache row."""
    block_bytes = 2 * bs * hkd * cache_bytes
    g = DECODE_SEQS_PER_GROUP
    c = max(1, min(DECODE_CHUNK_BYTES // block_bytes,
                   DECODE_MAX_BLOCKS_PER_CHUNK))
    copies = 2 if cache_bytes == 1 else 1  # int8: a scale tile a block
    while g * c * copies > DECODE_MAX_DMA_SITES and g > 1:
        g //= 2
    while (decode_vmem_bytes(g, c, rows, hkd, bs, cache_bytes, q_bytes)
           > SCOPED_VMEM_BYTES and (g > 1 or c > 1)):
        if c > 1 and c * 2 >= g:
            c //= 2
        else:
            g //= 2
    return g, c


def decode_group_and_chunk(b: int, s_q: int, m: int, seqs_per_group: int,
                           blocks_per_chunk: int) -> tuple[int, int]:
    """(G, C) one call of the flash-decode kernel runs with at B rows of
    S queries and a table of M blocks: the VMEM scratch scales with S*H
    query rows, so the group shrinks by S; it must divide the batch
    (terminates at 1); a chunk is no longer than the table."""
    g = max(1, seqs_per_group // s_q)
    while b % g:
        g -= 1
    return g, min(blocks_per_chunk, m)


def decode_seqs_per_update(g: int, c: int, bs: int,
                           asked: int | None = None) -> int:
    """R, the sequences of a group whose flash update of a chunk is ONE
    batched pair of matmuls in one basic block: a row-chunk's update is a
    serial chain (matmul, max, exp, sum, matmul, accumulator) whose
    latencies only another sequence's chain can fill, so R chains run
    side by side.  The R sequences run to the longest's last chunk, the
    others masked, and a chunk of few keys drags a shorter sequence
    through more of them: R is ``DECODE_SEQS_PER_UPDATE`` where a chunk
    holds at least ``DECODE_KEYS_PER_UPDATE_SEQ`` keys for each, less
    below (4 at 128 keys and over, 2 at the 64 of 2,048 lanes) - or what
    a sweep ``asked`` for - and divides the group."""
    r = asked or min(DECODE_SEQS_PER_UPDATE,
                     c * bs // DECODE_KEYS_PER_UPDATE_SEQ)
    r = max(1, min(r, g))
    while g % r:
        r -= 1
    return r


def decode_kernel_cost(
    b: int, s_q: int, h: int, hk: int, d: int, bs: int, m: int,
    lens, cache_bytes: int = 2, quant: bool = False, q_bytes: int = 2,
    blocks_per_chunk: int = DECODE_BLOCKS_PER_CHUNK,
) -> dict:
    """Analytic cost of one flash-decode dispatch: every row's own blocks
    by DMA (ceil(len / Bs) of them: a block is copied only if the row owns
    it, nothing for an empty slot), the blocked block-diagonal q in and
    the output back (both ``q_bytes`` an element), and QK+PV FLOPs and
    softmax exps over the chunks a row computes (ceil(len / (C*Bs)): the
    two matmuls take a whole chunk).  ``lens`` is the per-row context;
    pass ``[m * bs] * b`` for the worst-case static bound
    (cost_estimate=)."""
    hkd = hk * d
    rows = s_q * h
    t = min(blocks_per_chunk, m) * bs
    block_bytes = 2 * bs * hkd * cache_bytes
    if quant:
        from dynamo_tpu.ops.kv_quant import scale_tile

        hp, sp = scale_tile(hk, bs)
        block_bytes += 2 * hp * sp * 4
    lens = [int(x) for x in lens]
    blocks = sum(_cdiv(n, bs) for n in lens)
    chunks = sum(_cdiv(n, t) for n in lens)
    dma = blocks * block_bytes
    dma += b * rows * hkd * 2 * q_bytes  # q in + out
    return _cost_dict(dma, chunks * 4 * rows * t * hkd,  # QK + PV matmuls
                      chunks * rows * t)                 # softmax exp


def prefill_rows_per_chunk(heads: int) -> int:
    """Tokens a grid step of the prefill kernel takes for ``heads`` query
    heads (a shard's): 128 up to 32 heads, fewer beyond, so that the
    scratch stays inside scoped VMEM (64 heads at 128 tokens ask for 22 MB
    of 16)."""
    return min(PREFILL_ROWS_PER_CHUNK, max(8, PREFILL_QUERY_ROWS // heads))


def prefill_kernel_cost(
    b: int, s: int, h: int, hk: int, d: int, bs: int, m: int,
    starts, cache_bytes: int = 2, quant: bool = False, q_bytes: int = 2,
    rows_per_chunk: int = PREFILL_ROWS_PER_CHUNK,
    blocks_per_chunk: int = PREFILL_BLOCKS_PER_CHUNK,
) -> dict:
    """Analytic cost of one flash-prefill dispatch.  Each of the S/TQ
    row-chunks of a row re-streams that row's cached prefix (the kernel
    restarts the prefix walk per grid step); the fresh phase is the
    causal triangle.  ``starts`` is the per-row prefix length (pass
    ``[m * bs] * b`` for the worst-case static bound)."""
    g = h // hk
    hkd = hk * d
    tq = min(rows_per_chunk, s)
    while s % tq:
        tq //= 2
    c = min(blocks_per_chunk, m)
    t = c * bs
    n_steps = s // tq
    rows = tq * g
    block_bytes = 2 * bs * hkd * cache_bytes
    if quant:
        from dynamo_tpu.ops.kv_quant import scale_tile

        hp, sp = scale_tile(hk, bs)
        block_bytes += 2 * hp * sp * 4
    dma = flops = trans = 0
    for start in [int(x) for x in starts]:
        p = _cdiv(start, t)
        dma += n_steps * p * c * block_bytes
        flops += n_steps * p * hk * 4 * rows * t * d
        trans += n_steps * p * hk * rows * t
    # fresh phase: step ri visits ri+1 TQ-sized K/V chunks (causal)
    tri = n_steps * (n_steps + 1) // 2
    flops += b * tri * hk * 4 * rows * tq * d
    trans += b * tri * hk * rows * tq
    # blocked traffic: q/out per step; fresh K/V re-fetched per batch row
    dma += b * n_steps * tq * hkd * g // hk * 0  # (kept explicit below)
    dma += b * n_steps * (tq * g * d * hk // hk) * 0
    dma += b * n_steps * tq * g * d * hk * 0
    dma += b * n_steps * hk * tq * g * d * (q_bytes + q_bytes)  # q + out
    dma += b * 2 * s * hkd * cache_bytes  # fresh K and V, once per row
    return _cost_dict(dma, flops, trans)


def ragged_kernel_cost(
    t_tokens: int, h: int, hk: int, d: int, bs: int, m: int,
    starts, cache_bytes: int = 2, quant: bool = False, q_bytes: int = 2,
    rows_per_chunk: int = PREFILL_ROWS_PER_CHUNK,
    blocks_per_chunk: int = PREFILL_BLOCKS_PER_CHUNK,
) -> dict:
    """Analytic cost of one ragged (mixed-chunk) dispatch: grid T/TQ;
    EVERY grid step walks every overlapping row's prefix — the audit
    prices the conservative bound where each step streams each row's
    full prefix (the kernel skips non-overlapping rows, so the true
    cost is lower for well-packed batches)."""
    g = h // hk
    hkd = hk * d
    tq = min(rows_per_chunk, t_tokens)
    while t_tokens % tq:
        tq //= 2
    c = min(blocks_per_chunk, m)
    t = c * bs
    n_steps = t_tokens // tq
    rows = tq * g
    block_bytes = 2 * bs * hkd * cache_bytes
    if quant:
        from dynamo_tpu.ops.kv_quant import scale_tile

        hp, sp = scale_tile(hk, bs)
        block_bytes += 2 * hp * sp * 4
    dma = flops = trans = 0
    for start in [int(x) for x in starts]:
        p = _cdiv(start, t)
        dma += n_steps * p * c * block_bytes
        flops += n_steps * p * hk * 4 * rows * t * d
        trans += n_steps * p * hk * rows * t
    tri = n_steps * (n_steps + 1) // 2
    flops += tri * hk * 4 * rows * tq * d
    trans += tri * hk * rows * tq
    dma += n_steps * hk * tq * g * d * (q_bytes + q_bytes)  # q + out
    dma += 2 * t_tokens * hkd * cache_bytes  # packed fresh K and V
    return _cost_dict(dma, flops, trans)


def int8_matmul_cost(
    m: int, k: int, n: int, x_bytes: int = 2, out_bytes: int = 2,
    bm: int = INT8_MATMUL_BM, bn: int = INT8_MATMUL_BN,
    bk: int = INT8_MATMUL_BK,
) -> dict:
    """Analytic cost of one dequant-in-kernel int8 matmul: the weight
    tile streams as int8 (the whole point), x tiles re-stream per N
    block, outputs write once."""
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    gm, gn, gk = m // bm, n // bn, k // bk
    dma = (
        gm * gn * gk * (bm * bk * x_bytes + bk * bn * 1)  # x bf16 + w int8
        + gm * gn * (bm * bn * out_bytes + bn * 4)        # out + scale
    )
    return _cost_dict(dma, 2 * m * n * k, 0)


def mla_sparse_cost(n: int, h: int, w: int, k: int) -> dict:
    """Sparse latent attention over N queries of K listed rows of W words,
    every list full: each row is one 4·W-byte DMA; both halves of a row are
    scored (2 · 2·H·W·K) and summed (2 · 2·H·W·K); one exp a (head, row)."""
    rows = n * k
    return _cost_dict(
        dma=rows * 4 * w + n * k * 4 + 2 * n * h * w * 2 + 2 * n * h * w * 4,
        flops=8 * h * w * rows, trans=h * rows)


def mla_masked_cost(s: int, c: int, h: int, dq: int, dv: int,
                    tq: int = MLA_MASKED_TOKENS_PER_TILE,
                    tk: int = MLA_MASKED_KEYS_PER_TILE,
                    live: int | None = None, ctx: int | None = None) -> dict:
    """Masked latent prefill of S tokens over C positions of which ``live``
    and ``ctx`` exist (None: all): in the live tiles every (query row, key)
    pair is scored over Dq and summed over Dv; the live part of the context
    and of the bias is read once a live query tile, every output row is
    written."""
    sl = tq * _cdiv(s if live is None else live, tq)
    cl = tk * _cdiv(c if ctx is None else ctx, tk)
    pairs = sl * h * cl
    return _cost_dict(
        dma=(sl // tq) * cl * dq * 2 + sl * cl * 4 + sl * h * dq * 2
        + s * h * dv * 4,
        flops=2 * pairs * (dq + dv), trans=pairs)


def dsa_index_vmem_bytes(b: int, cols: int, h: int, d: int, t: int,
                         g: int) -> int:
    """VMEM of one ``dsa_index_scores`` call: the rows' scores [B, cols],
    queries [B, H, D] and lane-broadcast head weights [B, H, 128] as whole
    blocks (two buffers each), the stacked members' copies, two key chunks of
    T keys, and a group's products [G·H, T] in f32 with as much again for
    what is made of them."""
    blocks = b * cols * 4 + b * h * d * 2 + b * h * 128 * 4
    scratch = g * h * d * 2 + g * h * 128 * 4 + DOUBLE_BUFFER * t * d * 2
    return DOUBLE_BUFFER * blocks + scratch + 2 * g * h * t * 4


def dsa_index_cost(b: int, h: int, d: int, cols: int, fetched: int,
                   scored: int) -> dict:
    """Index scores of a decode step of B rows over ``cols`` table
    positions: ``fetched`` keys of D bf16 elements move (a group's shared
    keys once), ``scored`` (row, key) pairs take H dot products of D and a
    relu, a weight and a sum each; queries and weights are read and every
    row's scores written once."""
    return _cost_dict(
        dma=fetched * d * 2 + b * cols * 4 + b * h * d * 2 + b * h * 128 * 4,
        flops=scored * h * (2 * d + 3), trans=0)


def masked_prefill_is_cheaper(context: int, topk: int) -> bool:
    """Whether a prefill chunk of one sequence over a static context of
    ``context`` positions, each query selecting at most ``topk`` of them,
    attends faster masked than by gathering, a token: the masked kernel
    walks ``context`` / tk key tiles for the tq tokens of a query tile, the
    gather issues one DMA a selected row.  At 7.3 us a tile and 26 ns a row
    the forms cross near 60 k positions for 2,048 selected."""
    masked = (_cdiv(context, MLA_MASKED_KEYS_PER_TILE) * MLA_MASKED_TILE_NS
              / MLA_MASKED_TOKENS_PER_TILE)
    return masked < min(topk, context) * MLA_SPARSE_ROW_NS


def linear_state_heads_per_step(heads: int) -> int | None:
    """Heads a grid step of the state update takes: the most, up to
    ``LINEAR_STATE_HEADS_PER_STEP``, that divide ``heads`` into whole
    (8, 128) tiles of their vectors; None where no such group exists."""
    for group in range(LINEAR_STATE_HEADS_PER_STEP, 0, -8):
        if heads % group == 0:
            return group
    return None


def linear_state_cost(rows: int, heads: int, dk: int, dv: int) -> dict:
    """A decode row reads and writes each head's float32 matrix once
    (cellbench/costs/linear_state.py counts the same 2·H·dk·dv·4 bytes) and
    spends ~7 operations an element: the decay, two products with their
    sums, the rank-one update."""
    cells = rows * heads * dk * dv
    return _cost_dict(dma=2 * cells * 4 + rows * heads * (3 * dk + 2 * dv) * 4,
                      flops=7 * cells, trans=rows * heads * dk)


def ssm_state_heads_per_step(heads: int, groups: int, p: int,
                             n: int) -> int | None:
    """Heads a grid step of the state-space update takes: the most, up to
    ``SSM_STATE_BLOCK_BYTES`` of float32 matrices and the 128 a turned tile
    of x rows holds, that fill whole sublane tiles of 128-lane x rows and
    divide the ``heads / groups`` heads which share one B and C; None where
    no such group exists."""
    if heads % groups or 128 % p:
        return None
    tile = 8 * 128 // p             # heads whose x rows are one (8, 128) tile
    most = min(SSM_STATE_BLOCK_BYTES // (p * n * 4), 128) // tile * tile
    for group in range(most, 0, -tile):
        if (heads // groups) % group == 0:
            return group
    return None


def ssm_state_cost(rows: int, heads: int, p: int, n: int,
                   groups: int = 1) -> dict:
    """A decode row reads and writes each head's float32 matrix once beside
    its x, y, B, C and Δ (cellbench/costs/ssm_state.py counts the same) and
    spends ~5 operations an element: the decay, the rank-one update, the
    read-out."""
    cells = rows * heads * p * n
    return _cost_dict(
        dma=2 * cells * 4 + rows * (2 * heads * p + 2 * groups * n + heads) * 4,
        flops=5 * cells, trans=rows * heads)


def selective_step_cost(rows: int, n: int, channels: int) -> dict:
    """A decode row reads and writes its float32 state [N, channels] once
    beside its x, Δ and y rows and its B and C
    (cellbench/costs/selective_step.py counts the state's bytes the same) and
    spends ~6 operations and one exponential an element: Δ A, the decay, the
    update's two, the read-out's two."""
    cells = rows * n * channels
    return _cost_dict(dma=2 * cells * 4 + rows * (3 * channels + 2 * n) * 4,
                      flops=6 * cells, trans=cells)


def selective_scan_cost(rows: int, s: int, n: int, channels: int) -> dict:
    """A prefill chunk of ``s`` tokens a row reads and writes the row's state
    once, reads x̂ and Δ and writes y a token (float32), B and C a token, and
    spends the decode step's operations a token and state element."""
    cells = rows * s * n * channels
    return _cost_dict(
        dma=2 * rows * n * channels * 4 + rows * s * (3 * channels + 2 * n) * 4,
        flops=6 * cells, trans=cells)


def grouped_matmul_row_tile(m: int, k: int, x_bytes: int = 2) -> int:
    """Sorted rows a row tile of the grouped matmul at ``k`` columns — the
    wider of a layer's two (Dm and F), so that one plan serves its three
    projections: ``GROUPED_MATMUL_ROW_TILE``, halved while a tile passes
    ``GROUPED_MATMUL_ROW_TILE_BYTES``."""
    tm = GROUPED_MATMUL_ROW_TILE
    while tm > 16 and tm * k * x_bytes > GROUPED_MATMUL_ROW_TILE_BYTES:
        tm //= 2
    return min(tm, m)


def grouped_matmul_vmem_bytes(tm: int, tn: int, k: int, w_bytes: int = 2,
                              x_bytes: int = 2, weights: int = 1) -> int:
    """VMEM a grid step of the grouped matmul holds: the pipeline's two
    buffers of the row tile and, for each of the ``weights`` stacks the
    call multiplies it with, of the weight block and the output tile, a
    float32 product a stack before it is rounded, and the compiler's own."""
    return (DOUBLE_BUFFER * (tm * k * x_bytes + weights * (
        k * tn * w_bytes + tm * tn * x_bytes)) + weights * tm * tn * 4
        + GROUPED_MATMUL_COMPILER_VMEM_BYTES)


def grouped_matmul_tiling(tm: int, k: int, n: int, w_bytes: int = 2,
                          x_bytes: int = 2, weights: int = 1) -> int:
    """TN of the grouped matmul at TM rows a tile.  A weight block is the
    whole K and the widest slice of N (whole lanes, dividing N) of at most
    ``GROUPED_MATMUL_BLOCK_BYTES``: sized by its bytes, so that a grid
    step's fixed cost stays a small share of its DMA whatever the expert's
    shape (down: 3.1 MB whole in Qwen3, 5.2 of Solar's 10.5, 4.2 of
    Mistral-Small-4's 16.8 and of GLM's 25.2, 3.1 of granite-4.0-h-small's
    6.3), narrowed further while a step's buffers pass the scoped VMEM of
    one kernel (a call over two stacks, gate and up, holds a block of each:
    3.1 MB whole in Qwen3, 2.1 in Solar and Mistral-Small-4, 3.1 in GLM and,
    at K 4,096 / N 768, in granite-4.0-h-small: half of its N)."""
    lanes = 128
    if n % lanes:
        return n
    slices = [t for t in range(lanes, n + 1, lanes) if n % t == 0]
    fits = [t for t in slices
            if k * t * w_bytes <= GROUPED_MATMUL_BLOCK_BYTES
            and grouped_matmul_vmem_bytes(tm, t, k, w_bytes, x_bytes, weights)
            <= SCOPED_VMEM_BYTES]
    return max(fits, default=lanes)


def grouped_matmul_pairs(m: int, groups: int, tm: int) -> int:
    """The static length of a plan: every row tile has a pair, and each
    group with rows adds at most one (it starts inside a tile another
    opened, or opens its own)."""
    return _cdiv(m, tm) + max(min(groups, m) - 1, 0)


def grouped_matmul_cost(m: int, touched: int, k: int, n: int,
                        w_bytes: int = 2, x_bytes: int = 2,
                        weights: int = 1) -> dict:
    """Grouped matmul of ``m`` sorted rows with each of ``weights`` stacks
    over ``touched`` experts with rows: each one's K x N weights once, the
    rows in once and out once a stack, two operations a row and weight."""
    return _cost_dict(
        dma=weights * (touched * k * n * w_bytes + m * n * x_bytes)
        + m * k * x_bytes, flops=2 * weights * m * k * n, trans=0)


def latent_dma_cost(rows: int, row_bytes: int) -> dict:
    """The latent cache's movers read and write every moved row once."""
    return _cost_dict(dma=2 * rows * row_bytes, flops=0, trans=0)


def _cost_dict(dma: int, flops: int, trans: int) -> dict:
    return {
        "hbm_bytes": int(dma),
        "flops": int(flops),
        "transcendentals": int(trans),
        "intensity": round(flops / dma, 4) if dma else 0.0,
    }


def _cost_estimate(cost: dict):
    """dict -> pl.CostEstimate."""
    from jax.experimental import pallas as pl

    return pl.CostEstimate(
        flops=cost["flops"],
        transcendentals=cost["transcendentals"],
        bytes_accessed=cost["hbm_bytes"],
    )


def decode_cost_estimate(b, s_q, h, hk, d, bs, m, cache_bytes, quant,
                         blocks_per_chunk, q_bytes=2, window=None):
    """Worst-case (full-table context) CostEstimate for the decode
    pallas_call — seq_lens are dynamic at trace time, so the static
    bound is every row at M*Bs context, or at the ``window`` and the block
    its older edge falls in where the kernel walks a window."""
    span = m * bs if window is None else min(m * bs, window + bs)
    return _cost_estimate(decode_kernel_cost(
        b, s_q, h, hk, d, bs, m, [span] * b, cache_bytes=cache_bytes,
        quant=quant, q_bytes=q_bytes, blocks_per_chunk=blocks_per_chunk,
    ))


def prefill_cost_estimate(b, s, h, hk, d, bs, m, cache_bytes, quant,
                          rows_per_chunk, blocks_per_chunk, window=None):
    """Worst case for the prefill pallas_call: a full-table prefix, or the
    ``window`` of it a windowed call streams."""
    span = m * bs if window is None else min(m * bs, window)
    return _cost_estimate(prefill_kernel_cost(
        b, s, h, hk, d, bs, m, [span] * b, cache_bytes=cache_bytes,
        quant=quant, rows_per_chunk=rows_per_chunk,
        blocks_per_chunk=blocks_per_chunk,
    ))


def ragged_cost_estimate(t_tokens, r_rows, h, hk, d, bs, m, cache_bytes,
                         quant, rows_per_chunk, blocks_per_chunk,
                         window=None):
    span = m * bs if window is None else min(m * bs, window)
    return _cost_estimate(ragged_kernel_cost(
        t_tokens, h, hk, d, bs, m, [span] * r_rows,
        cache_bytes=cache_bytes, quant=quant,
        rows_per_chunk=rows_per_chunk, blocks_per_chunk=blocks_per_chunk,
    ))


# -------------------------------------------------- cross-plane census ----


def fallback_census() -> dict:
    """The XLA-fallback collective census the shard plane accepted: the
    CPU decode probes gather the paged cache because the Pallas kernels
    (which keep it on-chip) don't lower there.  kerncheck asserts these
    stay in sync with shard_manifest.json's accepted SH002 entries
    (KN006) — retiring a kernel, or landing the unified kernel, must
    update BOTH planes deliberately."""
    return {
        "probe.llama.decode[tiny-llama]": {"all-gather": 4, "all-to-all": 1},
        "probe.deepseek.decode[tiny-mla]": {"all-gather": 10},
    }


def probe_coverage() -> dict:
    """kernel -> probed?  True when benchmarks/probe_kernels.py builds a
    variant from this registry's probe builders (satellite: a registered
    kernel without a probe is a KN006 finding).  Placeholders carry no
    probe by definition."""
    return {
        name: (name in _PROBE_BUILDERS or meta["placeholder"])
        for name, meta in KERNELS.items()
    }


# ------------------------------------------------------ input builders ----


def quantize_audit_cache(cache, hk: int):
    """f32 cache [L, N, 2, Bs, Hk*D] -> QuantKvCache with the canonical
    token-minor tile-padded scale layout."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.kv_quant import (
        QuantKvCache,
        pad_scales,
        quantize_kv_rows,
    )

    L, n, _, bs, hkd = cache.shape
    d = hkd // hk
    q8, sc = quantize_kv_rows(cache.reshape(L, n, 2, bs, hk, d))
    data = q8.reshape(L, n, 2, bs, hkd)
    sc = jnp.swapaxes(sc, -1, -2)  # [..., Hk, Bs] token-minor
    return QuantKvCache(data, pad_scales(sc))


def _np():
    import numpy as np

    return np


def _poison_cache(cache, bt, valid, bs):
    """NaN-poison a f32/bf16 cache: every slot of every unreferenced
    block, and every slot at/past ``valid[r]`` inside row r's blocks.
    (valid = seq_len for decode, prefix start for prefill/ragged.)"""
    np = _np()
    c = np.asarray(cache, np.float32)
    poisoned = np.full_like(c, np.nan)
    for r in range(bt.shape[0]):
        for ti in range(bt.shape[1]):
            bid = int(bt[r, ti])
            keep = max(0, min(bs, int(valid[r]) - ti * bs))
            if keep:
                poisoned[:, bid, :, :keep] = c[:, bid, :, :keep]
    return poisoned


def _poison_scales(scale, bt, valid, hk, bs):
    """Same poison for the quant scale pool [L, N, 2, Hp, Sp]: the pad
    lanes go NaN too — the kernels slice [:hk, :bs] value-level, and
    that slice is what keeps the poison out."""
    np = _np()
    s = np.asarray(scale, np.float32)
    poisoned = np.full_like(s, np.nan)
    for r in range(bt.shape[0]):
        for ti in range(bt.shape[1]):
            bid = int(bt[r, ti])
            keep = max(0, min(bs, int(valid[r]) - ti * bs))
            if keep:
                poisoned[:, bid, :, :hk, :keep] = s[:, bid, :, :hk, :keep]
    return poisoned


def _disjoint_tables(rows: int, m: int, n: int):
    """One disjoint block-id table per row, skipping block 0 so the
    clamp-path reads of padding table slots (which the engine leaves 0)
    hit an unreferenced — poisoned — block if they ever load."""
    np = _np()
    assert rows * m + 1 <= n, (rows, m, n)
    return (1 + np.arange(rows * m, dtype=np.int32)).reshape(rows, m)


# Audit dims shared by the small attention cases: tiny enough for
# interpret mode on CPU inside the tier-1 budget, shaped enough (GQA,
# multi-block tables, two layers) to exercise every index path.
_L, _BS, _HK, _D, _H, _M = 2, 8, 2, 16, 4, 4
_HKD = _HK * _D


def _decode_case(name: str, quant: bool, s_q: int = 1) -> dict:
    import jax.numpy as jnp

    np = _np()
    b = 8 if s_q == 1 else 4
    n = b * _M + 1
    layer = 1
    if s_q == 1:
        # empty row, 1-token row, block-exact, non-divisible, max-table
        lens = np.asarray([1, _M * _BS, 11, 0, _BS, 5, 17, 29], np.int32)
    else:
        # multi-query rows with non-block-aligned first-query positions
        lens = np.asarray([7, _M * _BS, 2, 19], np.int32)

    def build():
        rng = np.random.default_rng(101 if quant else 100)
        cache = jnp.asarray(
            rng.normal(size=(_L, n, 2, _BS, _HKD)), jnp.float32)
        bt = _disjoint_tables(b, _M, n)
        q = jnp.asarray(rng.normal(size=(b, s_q, _H, _D)), jnp.float32)
        kcache = quantize_audit_cache(cache, _HK) if quant else cache
        return {
            "q": q, "cache": kcache, "clean": cache,
            "bt": jnp.asarray(bt), "bt_np": bt, "lens": jnp.asarray(lens),
            "layer": jnp.int32(layer), "q0": jnp.asarray(lens - s_q),
        }

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.kv_quant import QuantKvCache
        from dynamo_tpu.ops.pallas.decode_attention import (
            paged_decode_attention_mq,
        )

        cache = inp["cache"]
        if poisoned:
            if quant:
                cache = QuantKvCache(cache.data, _np().asarray(
                    _poison_scales(cache.scale, inp["bt_np"], lens,
                                   _HK, _BS)))
            else:
                cache = _np().asarray(
                    _poison_cache(cache, inp["bt_np"], lens, _BS),
                    dtype=_np().float32)
        return paged_decode_attention_mq.__wrapped__(
            inp["q"], cache, inp["layer"], inp["bt"], inp["lens"],
            inp["q0"], blocks_per_chunk=2, seqs_per_group=4,
            interpret=True,
        )

    def oracle(inp):
        import jax

        from dynamo_tpu.ops.kv_quant import dequant_layer_slice
        from dynamo_tpu.ops.paged_attention import paged_attention

        np = _np()
        cache = inp["cache"]
        if quant:
            data = jax.lax.dynamic_index_in_dim(
                cache.data, inp["layer"], axis=0, keepdims=False)
            sc = jax.lax.dynamic_index_in_dim(
                cache.scale, inp["layer"], axis=0, keepdims=False)
            layer_kv = dequant_layer_slice(data, sc, _HK)
        else:
            layer_kv = cache[layer]
        kc = layer_kv[:, 0].reshape(n, _BS, _HK, _D)
        vc = layer_kv[:, 1].reshape(n, _BS, _HK, _D)
        positions = (lens - s_q)[:, None] + np.arange(s_q)[None, :]
        ref = paged_attention(
            inp["q"], kc, vc, inp["bt"],
            inp["lens"], positions.astype(np.int32))
        live = np.broadcast_to(
            (lens >= s_q)[:, None, None, None], ref.shape).copy()
        zero = np.broadcast_to(
            (lens == 0)[:, None, None, None], ref.shape).copy()
        return np.asarray(ref), live, zero

    def pricing():
        return decode_kernel_cost(
            b, s_q, _H, _HK, _D, _BS, _M, lens, cache_bytes=1 if quant
            else 4, quant=quant, q_bytes=4, blocks_per_chunk=2)

    return {
        "name": name, "kernel": "paged_decode_attention_mq",
        "mode": "interpret", "atol": 2e-3 if quant else 2e-4,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _prefill_case(name: str = "prefill-bf16") -> dict:
    import jax.numpy as jnp

    np = _np()
    b, s, layer = 2, 16, 0
    n = b * _M + 1
    starts = np.asarray([8, 0], np.int32)   # 1-block prefix / no prefix
    lens = np.asarray([24, 13], np.int32)   # row 1: 3 padding tail rows

    def build():
        rng = np.random.default_rng(200)
        cache = jnp.asarray(
            rng.normal(size=(_L, n, 2, _BS, _HKD)), jnp.float32)
        bt = _disjoint_tables(b, _M, n)
        q = jnp.asarray(rng.normal(size=(b, s, _H, _D)), jnp.float32)
        k_new = jnp.asarray(rng.normal(size=(b, s, _HK, _D)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(b, s, _HK, _D)), jnp.float32)
        return {
            "q": q, "k": k_new, "v": v_new, "cache": cache,
            "bt": jnp.asarray(bt), "bt_np": bt,
            "lens": jnp.asarray(lens), "starts": jnp.asarray(starts),
            "layer": jnp.int32(layer),
        }

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention,
        )

        np = _np()
        q, k, v, cache = inp["q"], inp["k"], inp["v"], inp["cache"]
        if poisoned:
            cache = np.asarray(
                _poison_cache(cache, inp["bt_np"], starts, _BS),
                np.float32)
            fresh = (lens - starts)
            qp, kp, vp = (np.asarray(x, np.float32).copy()
                          for x in (q, k, v))
            for r in range(b):
                qp[r, fresh[r]:] = np.nan
                kp[r, fresh[r]:] = np.nan
                vp[r, fresh[r]:] = np.nan
            q, k, v = qp, kp, vp
        return paged_prefill_attention.__wrapped__(
            q, k, v, cache, inp["layer"], inp["bt"], inp["lens"],
            inp["starts"], rows_per_chunk=8, blocks_per_chunk=2,
            interpret=True,
        )

    def oracle(inp):
        import os

        from dynamo_tpu.ops.paged_attention import prefill_attention

        np = _np()
        os.environ["DYNAMO_DISABLE_PALLAS_PREFILL"] = "1"
        try:
            ref = prefill_attention(
                inp["q"], inp["k"], inp["v"], inp["cache"], inp["layer"],
                inp["bt"], inp["lens"], inp["starts"], prefix_blocks=1)
        finally:
            os.environ.pop("DYNAMO_DISABLE_PALLAS_PREFILL", None)
        fresh = lens - starts
        idx = np.arange(s)
        live = np.broadcast_to(
            (idx[None, :] < fresh[:, None])[:, :, None, None],
            ref.shape).copy()
        # padding rows are finite garbage the caller discards (they
        # still see the causal columns) — no zero claim
        return np.asarray(ref), live, np.zeros_like(live)

    def pricing():
        return prefill_kernel_cost(
            b, s, _H, _HK, _D, _BS, _M, starts, cache_bytes=4,
            q_bytes=4, rows_per_chunk=8, blocks_per_chunk=2)

    return {
        "name": name, "kernel": "paged_prefill_attention",
        "mode": "interpret", "atol": 2e-4,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


# The adversarial ragged row set (ISSUE matrix): empty row, 1-token
# decode row with a non-block-aligned start, non-block-divisible chunk,
# max-block row at the full table context.
_RAGGED_ROWS = (
    # (start, fresh)
    (8, 0),    # empty row: zero fresh tokens, span [x, x)
    (11, 1),   # decode row: 1 token, start NOT block-aligned
    (8, 13),   # non-block-divisible chunk length
    (24, 8),   # max-block row: full M*Bs context
)


def _ragged_geometry(rows, tq: int = 8):
    np = _np()
    starts = np.asarray([r[0] for r in rows], np.int32)
    fresh = np.asarray([r[1] for r in rows], np.int32)
    lens = starts + fresh
    roffs = np.concatenate([[0], np.cumsum(fresh)[:-1]]).astype(np.int32)
    total = int(fresh.sum())
    t_tokens = max(tq, _cdiv(total, tq) * tq)
    sid = np.full(t_tokens, -1, np.int32)
    for r in range(len(rows)):
        sid[roffs[r]:roffs[r] + fresh[r]] = r
    return starts, fresh, lens, roffs, sid, t_tokens


def _ragged_case(name: str, quant: bool, rows=_RAGGED_ROWS,
                 seed: int = 300, tq: int = 8) -> dict:
    import jax.numpy as jnp

    np = _np()
    r_rows = len(rows)
    starts, fresh, lens, roffs, sid, t_tokens = _ragged_geometry(rows, tq)
    n = r_rows * _M + 1
    layer = 1
    prefix_blocks = int(_cdiv(int(starts.max()), _BS)) if len(rows) else 0

    def build():
        rng = np.random.default_rng(seed + (1 if quant else 0))
        cache = jnp.asarray(
            rng.normal(size=(_L, n, 2, _BS, _HKD)), jnp.float32)
        bt = _disjoint_tables(r_rows, _M, n)
        q = jnp.asarray(
            rng.normal(size=(1, t_tokens, _H, _D)), jnp.float32)
        k_new = jnp.asarray(
            rng.normal(size=(1, t_tokens, _HK, _D)), jnp.float32)
        v_new = jnp.asarray(
            rng.normal(size=(1, t_tokens, _HK, _D)), jnp.float32)
        kcache = quantize_audit_cache(cache, _HK) if quant else cache
        return {
            "q": q, "k": k_new, "v": v_new, "cache": kcache,
            "clean": cache, "bt": jnp.asarray(bt), "bt_np": bt,
            "lens": jnp.asarray(lens), "starts": jnp.asarray(starts),
            "roffs": jnp.asarray(roffs),
            "sid": jnp.asarray(sid[None, :]), "layer": jnp.int32(layer),
        }

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.kv_quant import QuantKvCache
        from dynamo_tpu.ops.pallas.prefill_attention import (
            ragged_paged_prefill_attention,
        )

        np = _np()
        q, k, v, cache = inp["q"], inp["k"], inp["v"], inp["cache"]
        if poisoned:
            if quant:
                cache = QuantKvCache(cache.data, np.asarray(
                    _poison_scales(cache.scale, inp["bt_np"], starts,
                                   _HK, _BS)))
            else:
                cache = np.asarray(
                    _poison_cache(cache, inp["bt_np"], starts, _BS),
                    np.float32)
            qp, kp, vp = (np.asarray(x, np.float32).copy()
                          for x in (q, k, v))
            pad = sid < 0
            qp[0, pad] = np.nan
            kp[0, pad] = np.nan
            vp[0, pad] = np.nan
            q, k, v = qp, kp, vp
        return ragged_paged_prefill_attention.__wrapped__(
            q, k, v, cache, inp["layer"], inp["bt"], inp["lens"],
            inp["starts"], inp["roffs"], rows_per_chunk=tq,
            blocks_per_chunk=2, interpret=True,
        )

    def oracle(inp):
        import os

        from dynamo_tpu.ops.paged_attention import ragged_prefill_attention

        np = _np()
        os.environ["DYNAMO_DISABLE_PALLAS_PREFILL"] = "1"
        try:
            ref = ragged_prefill_attention(
                inp["q"], inp["k"], inp["v"], inp["cache"], inp["layer"],
                inp["bt"], inp["lens"], inp["starts"], inp["roffs"],
                inp["sid"], prefix_blocks)
        finally:
            os.environ.pop("DYNAMO_DISABLE_PALLAS_PREFILL", None)
        live = np.broadcast_to(
            (sid >= 0)[None, :, None, None], ref.shape).copy()
        return np.asarray(ref), live, np.zeros_like(live)

    def pricing():
        return ragged_kernel_cost(
            t_tokens, _H, _HK, _D, _BS, _M, starts,
            cache_bytes=1 if quant else 4, quant=quant, q_bytes=4,
            rows_per_chunk=tq, blocks_per_chunk=2)

    return {
        "name": name, "kernel": "ragged_paged_prefill_attention",
        "mode": "interpret", "atol": 2e-3 if quant else 2e-4,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _int8_matmul_case() -> dict:
    import jax.numpy as jnp

    np = _np()
    m, k, n = 256, 1024, 1024  # grid (2, 2, 2): revisits the K axis

    def build():
        rng = np.random.default_rng(400)
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        wq = jnp.asarray(
            rng.integers(-127, 128, size=(k, n)), jnp.int8)
        scale = jnp.asarray(
            rng.uniform(0.01, 0.1, size=(n,)), jnp.float32)
        return {"x": x, "wq": wq, "scale": scale}

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas.int8_matmul import int8_matmul

        return int8_matmul.__wrapped__(
            inp["x"], inp["wq"], inp["scale"], interpret=True)

    def oracle(inp):
        np = _np()
        x = np.asarray(inp["x"], np.float32)
        w = np.asarray(inp["wq"], np.float32)
        sc = np.asarray(inp["scale"], np.float32)
        ref = (x @ w) * sc[None, :]
        live = np.ones(ref.shape, bool)
        return ref, live, np.zeros_like(live)

    def pricing():
        return int8_matmul_cost(m, k, n)

    return {
        "name": "int8-matmul", "kernel": "int8_matmul",
        # bf16 x + K=1024 reduction: ~1.5% relative on O(100) outputs
        "mode": "interpret", "atol": 8.0,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _mla_sparse_case() -> dict:
    """Five queries of four heads over 48 listed rows, 16 a tile: a full
    list, lists ending inside a tile, one row, and an empty list.  The
    poisoned run points the padding past each list at NaN rows."""
    import jax.numpy as jnp

    np = _np()
    n, h, width, k, rows_total = 5, 4, 40, 48, 400
    nvalid = [48, 17, 1, 0, 33]

    def build():
        from dynamo_tpu.ops import latent_cache

        rng = np.random.default_rng(500)
        rows = rng.normal(size=(rows_total, width)).astype(np.float32)
        rows[-1] = np.nan                      # where poisoned padding points
        latent = latent_cache.pack_rows(jnp.asarray(rows, jnp.bfloat16))
        q = jnp.asarray(rng.normal(size=(n, h, width)) * 0.3, jnp.bfloat16)
        slots = rng.integers(0, rows_total - 1, size=(n, k)).astype(np.int32)
        return {"latent": latent[:, None, :], "q": q, "slots": slots,
                "nvalid": jnp.asarray(nvalid, jnp.int32)}

    def _lists(inp, poisoned):
        slots = inp["slots"].copy()
        for i, v in enumerate(nvalid):
            slots[i, v:] = rows_total - 1 if poisoned else slots[i, 0]
        return jnp.asarray(slots)

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops import latent_cache
        from dynamo_tpu.ops.pallas.mla_sparse_attention import (
            mla_sparse_attention,
        )

        q_lo, q_hi = latent_cache.split_query(inp["q"])
        o_lo, o_hi = mla_sparse_attention.__wrapped__(
            q_lo, q_hi, _lists(inp, poisoned), inp["nvalid"], inp["latent"],
            sm_scale=0.2, rows_per_tile=16, interpret=True)
        return jnp.concatenate([o_lo, o_hi], axis=-1)

    def oracle(inp):
        from dynamo_tpu.ops import latent_cache

        w = inp["latent"].shape[-1]
        ref = np.asarray(latent_cache.sparse_attention_xla(
            inp["q"], inp["latent"].reshape(1, rows_total, 1, 1, w), 0,
            _lists(inp, False), inp["nvalid"], 0.2), np.float32)
        live = np.ones(ref.shape, bool)
        zero = np.zeros(ref.shape, bool)
        zero[3] = True                         # the empty list
        return ref, live, zero

    def pricing():
        return mla_sparse_cost(n, h, 128, k)

    return {
        "name": "sparse-latent", "kernel": "mla_sparse_attention",
        "mode": "interpret", "atol": 2e-2,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _mla_masked_case() -> dict:
    """48 tokens of four heads over 384 keys in tiles of (8, 128), of which
    29 tokens and 200 keys exist: two dead query tiles, a dead key tile, a
    context and a chunk that end inside a tile; causal masks with a few more
    holes, one query with nothing selected in its first key tile, one with
    nothing at all.  The poisoned run makes the keys no query selects huge —
    a masked key weighs exactly nothing, but a cache row is an activation
    and never NaN, and 0 x NaN is what no matrix unit can mask — and the
    dead key tile, which no step reads, NaN."""
    import jax.numpy as jnp

    np = _np()
    s_, c, h, dq, dv = 48, 384, 4, 128, 128
    live, ctx_len = 29, 200

    def build():
        rng = np.random.default_rng(700)
        mask = np.tril(np.ones((s_, c), bool), k=ctx_len - live)
        mask &= rng.random((s_, c)) < 0.6
        mask[5, :128] = False
        mask[9] = False
        mask[:, 180:188] = False                 # keys nobody selects
        mask[live:] = False
        mask[:, ctx_len:] = False
        return {"q": jnp.asarray(rng.normal(size=(s_ * h, dq)) * 0.3,
                                 jnp.bfloat16),
                "ctx": rng.normal(size=(c, dq)).astype(np.float32),
                "mask": mask}

    def _ctx(inp, poisoned):
        ctx = inp["ctx"].copy()
        if poisoned:
            ctx[180:188] = 1e6
            ctx[256:] = np.nan
        return jnp.asarray(ctx, jnp.bfloat16)

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas.mla_masked_prefill import (
            mla_sparse_prefill_masked,
        )

        return mla_sparse_prefill_masked.__wrapped__(
            inp["q"], _ctx(inp, poisoned),
            jnp.where(jnp.asarray(inp["mask"]), 0.0, -1e30).astype(
                jnp.float32),
            jnp.asarray([live, ctx_len], jnp.int32),
            heads=h, dv=dv, sm_scale=0.2, tokens_per_tile=8,
            keys_per_tile=128, interpret=True)

    def oracle(inp):
        q = np.asarray(inp["q"], np.float32).reshape(s_, h, dq)
        ctx = np.asarray(_ctx(inp, False), np.float32)
        sc = np.einsum("shd,cd->shc", q, ctx) * 0.2
        sc = np.where(inp["mask"][:, None, :], sc, -np.inf)
        with np.errstate(invalid="ignore"):
            p = np.exp(sc - sc.max(axis=-1, keepdims=True))
            p = np.nan_to_num(p / p.sum(axis=-1, keepdims=True))
        ref = np.einsum("shc,cd->shd", p, ctx[:, :dv]).reshape(s_ * h, dv)
        alive = np.ones(ref.shape, bool)
        zero = np.zeros(ref.shape, bool)
        zero[9 * h:10 * h] = True
        zero[live * h:] = True
        return ref.astype(np.float32), alive, zero

    def pricing():
        return mla_masked_cost(s_, c, h, dq, dv, tq=8, tk=128, live=live,
                               ctx=ctx_len)

    return {
        "name": "masked-latent", "kernel": "mla_sparse_prefill_masked",
        "mode": "interpret", "atol": 2e-2,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _dsa_index_case() -> dict:
    """Seven rows of four heads over tables of twelve blocks of 32 keys,
    eight blocks a chunk (the table is not whole chunks): three rows on one
    document whose lengths end in different blocks, one of them the whole
    table, two on another that share ten blocks but one whole chunk, a row
    alone, an empty slot.  Only what a row sees is compared: the rest is
    whatever the buffers held.  The poisoned run makes every block no row
    owns NaN, and every key past a row's length in the blocks only it
    holds."""
    import jax.numpy as jnp

    np = _np()
    b, h, d, bs, m, n, c = 7, 4, 128, 32, 12, 64, 8
    doc_a, doc_b = list(range(3, 35, 4)), list(range(5, 45, 4))
    tables = np.zeros((b, m), np.int32)
    tables[0, :10] = doc_a + [44, 45]
    tables[1] = doc_a + [46, 47, 48, 49]
    tables[2, :9] = doc_a + [50]
    tables[3, :6] = range(52, 58)
    tables[5, :11] = doc_b + [58]
    tables[6] = doc_b + [59, 60]
    lens = np.array([298, 384, 258, 170, 0, 325, 382], np.int32)

    def build():
        rng = np.random.default_rng(900)
        return {"q": jnp.asarray(rng.normal(size=(b, h, d)), jnp.bfloat16),
                "w": jnp.asarray(rng.normal(size=(b, h)), jnp.bfloat16),
                "keys": rng.normal(size=(n, bs, d)).astype(np.float32)}

    def _keys(inp, poisoned):
        keys = inp["keys"].copy()
        if poisoned:
            owned = np.zeros(n, bool)
            for r in range(b):
                owned[tables[r, :-(-lens[r] // bs)]] = True
            keys[~owned] = np.nan
            for r in (0, 2, 3, 5, 6):          # a last block of its own
                keys[tables[r, lens[r] // bs], lens[r] % bs:] = np.nan
        return jnp.asarray(keys, jnp.bfloat16)

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas.dsa_index_scores import dsa_index_scores

        out = dsa_index_scores.__wrapped__(
            inp["q"], inp["w"], _keys(inp, poisoned), jnp.asarray(tables),
            jnp.asarray(lens), blocks_per_chunk=c, group_rows=4,
            interpret=True)
        seen = np.arange(m * bs)[None, :] < lens[:, None]
        return jnp.where(seen, out, 0.0)

    def oracle(inp):
        q = np.asarray(inp["q"], np.float32)
        w = np.asarray(inp["w"], np.float32)
        keys = np.asarray(_keys(inp, False), np.float32)[tables].reshape(
            b, m * bs, d)
        dots = np.einsum("bhd,bcd->bhc", q, keys)
        ref = np.einsum("bhc,bh->bc", np.maximum(dots, 0), w) * (h * d) ** -0.5
        seen = np.arange(m * bs)[None, :] < lens[:, None]
        return np.where(seen, ref, 0).astype(np.float32), seen, ~seen

    def pricing():
        from dynamo_tpu.ops.pallas.dsa_index_scores import index_keys_read

        return dsa_index_cost(
            b, h, d, 16 * bs,
            fetched=index_keys_read(tables, lens, bs, c, 4),
            scored=int((-(-lens // bs) * bs).sum()))

    return {
        "name": "index-scores", "kernel": "dsa_index_scores",
        "mode": "interpret", "atol": 2e-2,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _latent_dma_case(kind: str) -> dict:
    """``write``: seven rows of which two have no slot; ``gather``: five
    blocks, one twice.  Pure copies: the oracle is exact."""
    import jax.numpy as jnp

    np = _np()
    w, bs, blocks = 128, 4, 12

    def build():
        rng = np.random.default_rng(600)
        cache = rng.integers(0, 1 << 20, size=(blocks * bs, 1, w))
        rows = rng.integers(0, 1 << 20, size=(7, 1, w))
        return {"cache": jnp.asarray(cache, jnp.uint32),
                "rows": jnp.asarray(rows, jnp.uint32),
                "slots": jnp.asarray([5, -1, 0, 47, 9, -1, 30], jnp.int32),
                "ids": jnp.asarray([7, 2, 2, 11, 0], jnp.int32)}

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas import latent_cache_dma as dma

        if kind == "gather":
            return dma.gather_blocks.__wrapped__(
                inp["cache"].reshape(blocks, bs, 1, w), inp["ids"],
                interpret=True)
        rows = inp["rows"]
        if poisoned:                           # rows without a slot: junk
            rows = jnp.where((inp["slots"] < 0)[:, None, None],
                             jnp.uint32(0xFFFFFFFF), rows)
        return dma.write_rows.__wrapped__(
            jnp.array(inp["cache"]), rows, inp["slots"], interpret=True)

    def oracle(inp):
        cache = np.asarray(inp["cache"]).astype(np.float32)
        if kind == "gather":
            ref = cache.reshape(blocks, bs, 1, w)[np.asarray(inp["ids"])]
        else:
            ref = cache.copy()
            for r, s_ in zip(np.asarray(inp["rows"]), np.asarray(inp["slots"])):
                if s_ >= 0:
                    ref[s_] = r
        live = np.ones(ref.shape, bool)
        return ref, live, np.zeros_like(live)

    def pricing():
        return latent_dma_cost(5 * bs if kind == "gather" else 5, 4 * w)

    return {
        "name": f"latent-{kind}", "kernel": "latent_cache_dma",
        "mode": "interpret", "atol": 0.0,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def linear_state_rows(o, layer_state):
    """``o`` [B, H, dv] beside a layer's state [B, H, dk, dv], a row a slot."""
    import jax.numpy as jnp

    b = o.shape[0]
    return jnp.concatenate(
        [o.reshape(b, -1), layer_state.reshape(b, -1)], axis=1)


def _slot_step_reference(step, state, layer, *rest):
    """A recurrence's ``step`` (its vectors, then the state) on layer
    ``layer`` of a slot array under XLA, as ``linear_state_rows``: a fresh
    slot from zeros, a dead slot's state as it was and its output zero.
    ``rest``: the step's vectors, then ``fresh`` and ``alive`` [B]."""
    import jax.numpy as jnp

    *vectors, fresh, alive = rest
    old = state[layer]
    o, new = step(*vectors, jnp.where(fresh[:, None, None, None], 0, old))
    return linear_state_rows(jnp.where(alive[:, None, None], o, 0),
                             jnp.where(alive[:, None, None, None], new, old))


def linear_state_reference(state, layer, q, k, v, g, beta, fresh, alive):
    """What ``linear_state.state_update`` must give, by ``delta_rule_step``
    (``_slot_step_reference``)."""
    from dynamo_tpu.ops.linear_state import delta_rule_step

    return _slot_step_reference(delta_rule_step, state, layer, q, k, v, g,
                                beta, fresh, alive)


def ssm_state_reference(state, layer, x, dt, a_head, b, c, d, fresh, alive):
    """What ``ssm_state.state_update`` must give, by ``ssd_step``
    (``_slot_step_reference``)."""
    from dynamo_tpu.ops.ssm_state import ssd_step

    return _slot_step_reference(ssd_step, state, layer, x, dt, a_head, b, c,
                                d, fresh, alive)


def selective_step_reference(state, layer, x, dt, a, b, c, fresh, alive):
    """What ``selective_state.state_update`` must give, by ``selective_step``
    (``_slot_step_reference``; ``a`` [N, R, 128] is the layer's, not a
    row's, and goes in behind the row's x and Δ)."""
    from dynamo_tpu.ops.selective_state import selective_step

    step = lambda x, dt, b, c, st: selective_step(x, dt, a, b, c, st)
    return _slot_step_reference(step, state, layer, x, dt, b, c, fresh, alive)


def selective_scan_reference(state, layer, slots, x, dt, a, b, c, fresh):
    """What ``selective_state.state_scan`` must give, by ``selective_scan``
    on the rows' slots: ``y`` [B, S·R·128] beside each row's new state, a
    row a dispatch row (``linear_state_rows``)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.selective_state import selective_scan

    old = state[layer][slots]
    y, new = selective_scan(
        x, dt, a, b, c, jnp.where(fresh[:, None, None, None], 0, old))
    return linear_state_rows(y, new)


def _slot_state_case(name: str, kernel: str, vectors, state_shape: tuple,
                     reference, pricing) -> dict:
    """An audit case of a recurrent state's decode step: four slots, layer 1
    of a two-layer leaf ``state_shape`` [2, 4, H, ., .]; slot 1 starts
    afresh, slot 2 has no token.  The poisoned run fills both with NaN
    beforehand: the fresh row reads as from zeros, the dead one keeps its
    NaN (not live) and gives an output of exact zeros.  ``vectors(rng)``:
    the step's vectors in the kernel's order.  Output: ``o`` and the layer's
    new state, a row a slot."""
    import importlib

    import jax.numpy as jnp

    np = _np()
    layer = 1
    fresh = np.array([False, True, False, False])
    alive = np.array([True, True, False, True])
    module = KERNELS[kernel]["module"]

    def build():
        rng = np.random.default_rng(800)
        return {"vectors": tuple(jnp.asarray(v, jnp.float32)
                                 for v in vectors(rng)),
                "state": rng.normal(size=state_shape).astype(np.float32)}

    def run(inp, poisoned: bool):
        state = inp["state"].copy()
        if poisoned:
            state[layer, fresh | ~alive] = np.nan
        o, new = importlib.import_module(module).state_update.__wrapped__(
            jnp.asarray(state), jnp.int32(layer), *inp["vectors"],
            jnp.asarray(fresh), jnp.asarray(alive), interpret=True)
        return linear_state_rows(o, new[layer])

    def oracle(inp):
        ref = np.asarray(reference(
            jnp.asarray(inp["state"]), layer, *inp["vectors"],
            jnp.asarray(fresh), jnp.asarray(alive)))
        live = np.broadcast_to(alive[:, None], ref.shape).copy()
        zero = np.zeros(ref.shape, bool)
        # the columns of ``o``: the row less one slot's state
        zero[~alive, :ref.shape[1] - math.prod(state_shape[2:])] = True
        return ref, live, zero

    return {
        "name": name, "kernel": kernel, "mode": "interpret", "atol": 1e-5,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


def _linear_state_case() -> dict:
    """The delta rule's: eight heads of 128 x 128."""
    np = _np()
    b, h, d = 4, 8, 128
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)

    def vectors(rng):
        return (unit(rng.normal(size=(b, h, d))) * d ** -0.5,
                unit(rng.normal(size=(b, h, d))),
                rng.normal(size=(b, h, d)),
                -0.5 * rng.random(size=(b, h, d)),
                2 / (1 + np.exp(-rng.normal(size=(b, h)))))

    return _slot_state_case(
        "state-step", "linear_state_update", vectors, (2, b, h, d, d),
        linear_state_reference, lambda: linear_state_cost(b, h, d, d))


def _ssm_state_case() -> dict:
    """The state-space recurrence's: 32 heads of 64 x 128 in two groups of B
    and C, a grid step a group."""
    b, h, p, n, g = 4, 32, 64, 128, 2

    def vectors(rng):
        return (rng.normal(size=(b, h, p)), 0.1 * rng.random(size=(b, h)),
                -rng.uniform(1, 16, size=h), rng.normal(size=(b, g, n)),
                rng.normal(size=(b, g, n)), rng.normal(size=h))

    return _slot_state_case(
        "ssm-step", "ssm_state_update", vectors, (2, b, h, p, n),
        ssm_state_reference, lambda: ssm_state_cost(b, h, p, n, g))


def _selective_vectors(rng, lead: tuple, n: int, rows: int):
    """x, Δ [*lead, R, 128], A [N, R, 128], B, C [*lead, N] of the selective
    recurrence, in the kernels' order."""
    np = _np()
    a = -np.arange(1, n + 1, dtype=np.float32)[:, None, None] * np.ones(
        (n, rows, 128), np.float32)
    return (rng.normal(size=(*lead, rows, 128)),
            0.1 * rng.random(size=(*lead, rows, 128)), a,
            rng.normal(size=(*lead, n)), rng.normal(size=(*lead, n)))


def _selective_step_case() -> dict:
    """The selective recurrence's decode step: 16 numbers a channel, 1,024
    channels (eight register rows)."""
    b, n, rows = 4, 16, 8
    return _slot_state_case(
        "selective-step", "selective_state_update",
        lambda rng: _selective_vectors(rng, (b,), n, rows),
        (2, b, n, rows, 128), selective_step_reference,
        lambda: selective_step_cost(b, n, rows * 128))


def _selective_scan_case() -> dict:
    """The selective recurrence's prefill scan: two rows of 24 tokens (three
    token blocks of 8) into slots 2 and 0 of four, 2,048 channels (two
    channel tiles); row 0 starts afresh — the poisoned run fills its slot
    with NaN beforehand — and row 1's last 9 tokens are padding (Δ = 0).
    Output: ``y`` and each row's new state, a row a dispatch row."""
    import jax.numpy as jnp

    np = _np()
    layer, nb, s, n, rows = 1, 2, 24, 16, 16
    slots = np.array([2, 0], np.int32)
    fresh = np.array([True, False])

    def build():
        rng = np.random.default_rng(801)
        x, dt, a, b, c = _selective_vectors(rng, (nb, s), n, rows)
        dt[1, 15:] = 0.0
        return {"vectors": tuple(jnp.asarray(v, jnp.float32)
                                 for v in (x, dt, a, b, c)),
                "state": rng.normal(size=(2, 4, n, rows, 128)
                                    ).astype(np.float32)}

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas.selective_state import state_scan

        state = inp["state"].copy()
        if poisoned:
            state[layer, slots[fresh]] = np.nan
        y, new = state_scan.__wrapped__(
            jnp.asarray(state), jnp.int32(layer), jnp.asarray(slots),
            *inp["vectors"], jnp.asarray(fresh), interpret=True)
        return linear_state_rows(y, new[layer][slots])

    def oracle(inp):
        ref = np.asarray(selective_scan_reference(
            jnp.asarray(inp["state"]), layer, jnp.asarray(slots),
            *inp["vectors"], jnp.asarray(fresh)))
        return ref, np.ones(ref.shape, bool), np.zeros(ref.shape, bool)

    return {
        "name": "selective-scan", "kernel": "selective_state_scan",
        "mode": "interpret", "atol": 1e-5, "build": build, "run": run,
        "oracle": oracle,
        "pricing": lambda: selective_scan_cost(nb, s, n, rows * 128),
    }


def grouped_matmul_reference(xs, w, group_sizes, first_group=0):
    """What ``grouped_expert_matmul`` must give: ``lax.ragged_dot`` over the
    E groups at ``first_group`` of ``w``'s G, in float32, rows of no group
    zero (``ragged_dot`` leaves them unspecified)."""
    import jax
    import jax.numpy as jnp

    sizes = jnp.zeros(w.shape[0], jnp.int32).at[
        first_group:first_group + group_sizes.shape[0]].set(group_sizes)
    out = jax.lax.ragged_dot(jnp.nan_to_num(xs).astype(jnp.float32),
                             w.astype(jnp.float32), sizes)
    in_group = jnp.arange(xs.shape[0]) < group_sizes.sum()
    return jnp.where(in_group[:, None], out, 0)


def _grouped_matmul_case() -> dict:
    """40 sorted rows in tiles of 8 over layer 1's six experts of a stacked
    [3 x 6] array: offsets off the tiling (3, 8, 15), two empty groups, a
    group over two tiles, and 24 rows of no group, which the poisoned run
    fills with NaN: they come out exact zeros and touch no live row."""
    import jax.numpy as jnp

    np = _np()
    m, k, n, e, layers, layer = 40, 128, 256, 6, 3, 1
    sizes = np.asarray([3, 5, 7, 0, 0, 1], np.int32)
    total = int(sizes.sum())

    def build():
        rng = np.random.default_rng(900)
        return {"xs": rng.normal(size=(m, k)).astype(np.float32),
                "w": jnp.asarray(rng.normal(size=(layers * e, k, n)) * 0.1,
                                 jnp.float32),
                "sizes": jnp.asarray(sizes)}

    def run(inp, poisoned: bool):
        from dynamo_tpu.ops.pallas import grouped_matmul as gmm

        xs = inp["xs"].copy()
        if poisoned:
            xs[total:] = np.nan
        plan = gmm.grouped_matmul_plan(inp["sizes"], m, 8)
        return gmm.grouped_expert_matmul.__wrapped__(
            jnp.asarray(xs), (inp["w"],), plan, layer * e, tm=8, tn=128,
            interpret=True)[0]

    def oracle(inp):
        ref = np.asarray(grouped_matmul_reference(
            jnp.asarray(inp["xs"]), inp["w"], inp["sizes"], layer * e))
        live = np.zeros(ref.shape, bool)
        live[:total] = True
        return ref, live, ~live

    def pricing():
        return grouped_matmul_cost(m, 4, k, n, w_bytes=4, x_bytes=4)

    return {
        "name": "grouped-experts", "kernel": "grouped_expert_matmul",
        "mode": "interpret", "atol": 1e-4,
        "build": build, "run": run, "oracle": oracle, "pricing": pricing,
    }


# ---------------------------------------------- serving-scale (spec) ----


def _spec_grouped_matmul(name: str, m: int, layers: int, e: int, k: int,
                         n: int, weights: int) -> dict:
    """A cell's grouped matmul at its own widths, shape-traced: the weight
    blocks the tiling rule picks (``weights`` stacks a call), double
    buffered, inside the VMEM budget."""

    def build():
        import jax
        import jax.numpy as jnp

        f = jax.ShapeDtypeStruct
        return {"xs": f((m, k), jnp.bfloat16),
                "w": f((layers * e, k, n), jnp.bfloat16),
                "sizes": f((e,), jnp.int32), "first": f((), jnp.int32)}

    def run(inp, poisoned: bool):
        import jax

        from dynamo_tpu.ops.pallas import grouped_matmul as gmm

        def fn(xs, w, sizes, first):
            tm = grouped_matmul_row_tile(m, max(k, n))
            plan = gmm.grouped_matmul_plan(sizes, m, tm)
            return gmm.grouped_expert_matmul.__wrapped__(
                xs, (w,) * weights, plan, first, tm=tm)

        return jax.eval_shape(fn, inp["xs"], inp["w"], inp["sizes"],
                              inp["first"])

    def pricing():
        return grouped_matmul_cost(m, min(e, m), k, n, weights=weights)

    return {
        "name": name, "kernel": "grouped_expert_matmul", "mode": "spec",
        "build": build, "run": run, "oracle": None, "pricing": pricing,
    }



def _spec_decode_8b(name: str = "decode-8b", b=64, h=32, hk=8, d=128, bs=16,
                    n=4096, m=128, L=32) -> dict:
    """8B-serving decode shape (or a cell's own), shape-traced only: VMEM
    budget and pricing at the geometry that matters, without executing."""

    def build():
        import jax

        import jax.numpy as jnp

        f = jax.ShapeDtypeStruct
        return {
            "q": f((b, 1, h, d), jnp.bfloat16),
            "cache": f((L, n, 2, bs, hk * d), jnp.bfloat16),
            "layer": f((), jnp.int32),
            "bt": f((b, m), jnp.int32),
            "lens": f((b,), jnp.int32),
            "q0": f((b,), jnp.int32),
        }

    def run(inp, poisoned: bool):
        import jax

        from dynamo_tpu.ops.pallas.decode_attention import (
            paged_decode_attention_mq,
        )

        fn = functools.partial(
            paged_decode_attention_mq.__wrapped__, interpret=False)
        return jax.eval_shape(
            fn, inp["q"], inp["cache"], inp["layer"], inp["bt"],
            inp["lens"], inp["q0"])

    def pricing():
        return decode_kernel_cost(
            b, 1, h, hk, d, bs, m, [m * bs] * b, cache_bytes=2,
            q_bytes=2)

    return {
        "name": name, "kernel": "paged_decode_attention_mq",
        "mode": "spec", "build": build, "run": run, "oracle": None,
        "pricing": pricing,
    }


def _spec_prefill_8b(name: str = "prefill-8b", b=1, s=2048, h=32, hk=4,
                     d=128, bs=16, n=4096, m=128, L=32) -> dict:
    """S=2048 prefill at the documented serving tile (Hk*D=512), shape
    traced: this is the case the rows_per_chunk=128 VMEM claim is
    machine-checked against.  (Or a cell's own geometry.)"""

    def build():
        import jax

        import jax.numpy as jnp

        f = jax.ShapeDtypeStruct
        return {
            "q": f((b, s, h, d), jnp.bfloat16),
            "k": f((b, s, hk, d), jnp.bfloat16),
            "v": f((b, s, hk, d), jnp.bfloat16),
            "cache": f((L, n, 2, bs, hk * d), jnp.bfloat16),
            "layer": f((), jnp.int32),
            "bt": f((b, m), jnp.int32),
            "lens": f((b,), jnp.int32),
            "starts": f((b,), jnp.int32),
        }

    def run(inp, poisoned: bool):
        import jax

        from dynamo_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention,
        )

        fn = functools.partial(
            paged_prefill_attention.__wrapped__, interpret=False)
        return jax.eval_shape(
            fn, inp["q"], inp["k"], inp["v"], inp["cache"], inp["layer"],
            inp["bt"], inp["lens"], inp["starts"])

    def pricing():
        return prefill_kernel_cost(
            b, s, h, hk, d, bs, m, [m * bs] * b, cache_bytes=2,
            q_bytes=2)

    return {
        "name": name, "kernel": "paged_prefill_attention",
        "mode": "spec", "build": build, "run": run, "oracle": None,
        "pricing": pricing,
    }


def audit_cases() -> list[dict]:
    """The committed audit matrix: every non-placeholder kernel x its
    geometry cases.  Interpret cases run the NaN-canary differential on
    CPU; spec cases shape-trace only (VMEM + pricing)."""
    return [
        _decode_case("decode-bf16", quant=False),
        _decode_case("decode-int8", quant=True),
        _decode_case("decode-mq-unaligned", quant=False, s_q=2),
        _prefill_case(),
        _ragged_case("ragged-bf16", quant=False),
        _ragged_case("ragged-int8", quant=True),
        _int8_matmul_case(),
        _mla_sparse_case(),
        _mla_masked_case(),
        _dsa_index_case(),
        _latent_dma_case("write"),
        _latent_dma_case("gather"),
        _linear_state_case(),
        _ssm_state_case(),
        _selective_step_case(),
        _selective_scan_case(),
        _grouped_matmul_case(),
        _spec_decode_8b(),
        _spec_prefill_8b(),
        # Solar-Open2's decode (64 rows x top-8 over 20 held experts of
        # 4,096 x 1,280, gate and up in one call) and GLM-5.2's down
        # projection (the widest output tile)
        _spec_grouped_matmul("experts-solar-decode", 512, 8, 20, 4096, 1280,
                             weights=2),
        _spec_grouped_matmul("experts-glm-down", 256, 4, 16, 2048, 6144,
                             weights=1),
        # granite-4.0-h-small's decode (64 rows x top-10 over the 36 held
        # experts of 4,096 x 768: Qwen3's N at Solar's K, 8.9 rows an expert)
        _spec_grouped_matmul("experts-granite-decode", 640, 10, 36, 4096, 768,
                             weights=2),
        # ZAYA1-8B on one chip: 64 rows x top-1 over 16 wide experts of
        # 2,048 x 2,048 (gate and up in one call: N 4,096 of weights a
        # group, 4 rows an expert), and 8/2 heads of 128 in blocks of 32 —
        # a K/V row of 256 lanes, which only a --tp 4 shard had reached
        _spec_grouped_matmul("experts-zaya-decode", 64, 20, 16, 2048, 2048,
                             weights=2),
        _spec_decode_8b("decode-zaya", b=64, h=8, hk=2, bs=32, n=6272, L=20),
        _spec_prefill_8b("prefill-zaya", s=512, h=8, hk=2, bs=32, n=6272,
                         L=20),
        # AI21-Jamba2-3B's two attending layers: 20 query heads on ONE K/V
        # head of 128 in blocks of 32 — a K/V row of 128 lanes (one lane
        # tile: the least so far was 256) and 20 query rows a sequence, which
        # is not a multiple of 8 sublanes (every other cell has 4 or 8 a K/V
        # head): the query block, the accumulator and the m / l statistics
        # are whole-array blocks of 20 rows that Mosaic pads to 24
        _spec_decode_8b("decode-jamba", b=64, h=20, hk=1, bs=32, n=6272, L=2),
        _spec_prefill_8b("prefill-jamba", s=512, h=20, hk=1, bs=32, n=6272,
                         L=2),
    ]


def fuzz_case(seed: int) -> dict:
    """One seeded random ragged geometry for the nightly kern-fuzz
    sweep: rows drawn from the adversarial families (empty / 1-token
    decode / odd-length chunk / max-block), canary-checked against the
    oracle.  Deterministic per seed — the replay token is just the
    seed."""
    np = _np()
    rng = np.random.default_rng(seed)
    r_rows = int(rng.integers(2, 6))
    rows = []
    for _ in range(r_rows):
        kind = int(rng.integers(0, 4))
        if kind == 0:    # empty row
            rows.append((int(rng.integers(0, _M * _BS)), 0))
        elif kind == 1:  # decode row, any (non-aligned) start
            rows.append((int(rng.integers(0, _M * _BS - 1)), 1))
        elif kind == 2:  # odd-length chunk from a block-aligned start
            start = int(rng.integers(0, _M - 1)) * _BS
            fresh = int(rng.integers(1, _M * _BS - start + 1))
            rows.append((start, fresh))
        else:            # max-block row
            start = int(rng.integers(0, _M)) * _BS
            rows.append((start, _M * _BS - start))
    if all(f == 0 for _, f in rows):
        rows[0] = (0, 1)  # at least one real token so T > 0
    return _ragged_case(
        f"fuzz[ragged-{seed}]", quant=bool(rng.integers(0, 2)),
        rows=tuple(rows), seed=seed, tq=8)


# ------------------------------------------------------ probe builders ----
# chip_smoke.py and benchmarks/probe_kernels.py build their kernel probes
# from these, so probe coverage is registry coverage by construction.


def _probe_cache(rng, n, bs, hk, hd, dtype, quant):
    import jax.numpy as jnp

    cache = jnp.asarray(
        rng.normal(size=(1, n, 2, bs, hk * hd)), dtype)
    return quantize_audit_cache(cache, hk) if quant else cache


def probe_decode_inputs(batch, h, hk, hd, bs, n, bt_width, lens,
                        dtype=None, quant=False, s_q=0):
    """Concrete decode-probe inputs at serving dims (chip_smoke.py's
    kernel checks and probe_kernels.py's sweep share this).  With
    ``s_q > 0`` the multi-query shape is built instead: q gains a
    per-row query axis and a sixth element — the context lengths
    (``seq_lens - s_q``) the mq kernel takes — joins the tuple."""
    import jax.numpy as jnp

    np = _np()
    dtype = dtype or jnp.bfloat16
    rng = np.random.default_rng(0)
    qshape = (batch, s_q, h, hd) if s_q else (batch, h, hd)
    q = jnp.asarray(rng.normal(size=qshape), dtype)
    cache = _probe_cache(rng, n, bs, hk, hd, dtype, quant)
    bt = _probe_bt(batch, bt_width, n)
    lens = jnp.asarray(lens, jnp.int32)
    if s_q:
        return q, cache, jnp.int32(0), bt, lens, \
            jnp.maximum(lens - s_q, 0)
    return q, cache, jnp.int32(0), bt, lens


def probe_prefill_inputs(batch, s, h, hk, hd, bs, n, bt_width,
                         dtype=None, quant=False):
    import jax.numpy as jnp

    np = _np()
    dtype = dtype or jnp.bfloat16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(batch, s, hk, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(batch, s, hk, hd)), dtype)
    cache = _probe_cache(rng, n, bs, hk, hd, dtype, quant)
    # one cached block of prefix, clamped so prefix+fresh still fits
    # the block table (s == bt_width * bs means no prefix room)
    total = min(bs + s, bt_width * bs)
    lens = jnp.full((batch,), total, jnp.int32)
    starts = jnp.full((batch,), total - s, jnp.int32)
    return q, k, v, cache, jnp.int32(0), _probe_bt(batch, bt_width, n), \
        lens, starts


def _probe_bt(rows, bt_width, n):
    import jax.numpy as jnp

    np = _np()
    return jnp.asarray(
        np.arange(rows * bt_width).reshape(rows, bt_width) % n,
        jnp.int32)


def probe_ragged_inputs(t_tokens, r_rows, h, hk, hd, bs, n, bt_width,
                        dtype=None, quant=False):
    import jax.numpy as jnp

    np = _np()
    dtype = dtype or jnp.bfloat16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, t_tokens, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(1, t_tokens, hk, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(1, t_tokens, hk, hd)), dtype)
    cache = _probe_cache(rng, n, bs, hk, hd, dtype, quant)
    bt = _probe_bt(r_rows, bt_width, n)
    per = t_tokens // r_rows
    roffs = jnp.asarray(
        np.arange(r_rows, dtype=np.int32) * per, jnp.int32)
    # one cached block of prefix per row, clamped into the block table
    start = max(0, min(bs, bt_width * bs - per))
    starts = jnp.full((r_rows,), start, jnp.int32)
    lens = starts + per
    return q, k, v, cache, jnp.int32(0), bt, lens, starts, roffs


def probe_int8_matmul_inputs(m, k, n):
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(n,)), jnp.float32)
    return x, wq, scale


def probe_mla_sparse_inputs(n, h, width, k, rows_total, lens):
    """q [N,H,width], slots [N,K], nvalid [N], latent [1,R/Bs,Bs,1,W]: the
    operands of ``paged_attention.sparse_latent_attention`` (layer 0)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import latent_cache

    np = _np()
    rng = np.random.default_rng(0)
    bs = 32
    latent = latent_cache.pack_rows(jnp.asarray(
        rng.normal(size=(rows_total, width)), jnp.bfloat16))
    q = jnp.asarray(rng.normal(size=(n, h, width)) * 0.1, jnp.bfloat16)
    slots = jnp.asarray(rng.integers(0, rows_total, size=(n, k)), jnp.int32)
    nvalid = jnp.asarray(np.minimum(np.asarray(lens), k), jnp.int32)
    return (q, latent.reshape(1, rows_total // bs, bs, 1, -1), jnp.int32(0),
            slots, nvalid)


def probe_latent_dma_inputs(rows_total, width, t):
    """cache [R,1,W], rows [T,1,W], slots [T] (every third without one)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops import latent_cache

    np = _np()
    rng = np.random.default_rng(0)
    w = latent_cache.latent_words(width)
    cache = jnp.zeros((rows_total, 1, w), jnp.uint32)
    rows = jnp.asarray(rng.integers(0, 1 << 30, size=(t, 1, w)), jnp.uint32)
    slots = rng.permutation(rows_total)[:t].astype(np.int32)
    slots[::3] = -1
    return cache, rows, jnp.asarray(slots)


def probe_mla_masked_inputs(s, c, h, dq, live=None, ctx=None):
    """q [S·H, Dq], ctx [C, Dq], bias [S, C], lens [2]: the first ``live``
    of the S tokens end a context of ``ctx`` of the C positions (None: all
    of them); causal, every other key at random."""
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(0)
    live, ctx = s if live is None else live, c if ctx is None else ctx
    mask = np.tril(np.ones((s, c), bool), k=ctx - live)
    mask[:, 1::2] &= rng.random((s, c // 2)) < 0.5
    mask[live:] = False
    mask[:, ctx:] = False
    return (jnp.asarray(rng.normal(size=(s * h, dq)) * 0.1, jnp.bfloat16),
            jnp.asarray(rng.normal(size=(c, dq)), jnp.bfloat16),
            jnp.where(jnp.asarray(mask), 0.0, -1e30).astype(jnp.float32),
            jnp.asarray([live, ctx], jnp.int32))


def probe_dsa_index_inputs(rows, h, d, bs, m, docs, of, own, seed=0):
    """q [B, H, D], w [B, H], keys [R, Bs, D], tables [B, M], lens [B]: row r
    asks document ``of[r]`` (``docs``: their lengths in keys, whole blocks;
    the same block ids for every row that asks one) and adds ``own[r]`` keys
    of its own; ``of[r]`` < 0 is an empty slot."""
    import jax
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(seed)
    docs, of, own = (np.asarray(a, np.int64) for a in (docs, of, own))
    n = int((docs // bs).sum() + (-(-own // bs)).sum()) + 1
    free = iter(rng.permutation(n - 1) + 1)
    held = [[next(free) for _ in range(x // bs)] for x in docs]
    tables = np.zeros((rows, m), np.int32)
    lens = np.zeros(rows, np.int32)
    for r in range(rows):
        if of[r] < 0:
            continue
        ids = held[of[r]] + [next(free) for _ in range(-(-own[r] // bs))]
        tables[r, :len(ids)] = ids
        lens[r] = docs[of[r]] // bs * bs + own[r]
    kq, kw, kk = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (rows, h, d), jnp.bfloat16),
            jax.random.normal(kw, (rows, h), jnp.bfloat16),
            jax.random.normal(kk, (n, bs, d), jnp.bfloat16),
            jnp.asarray(tables), jnp.asarray(lens))


def probe_linear_state_inputs(layers, slots, heads, d):
    """state [L,B,H,d,d] f32, layer, q, k, v, g [B,H,d], beta [B,H], fresh,
    alive [B] (every eighth slot idle, one starting afresh)."""
    import jax
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    at = np.arange(slots)
    return (jax.random.normal(jax.random.PRNGKey(0),
                              (layers, slots, heads, d, d), jnp.float32),
            jnp.int32(layers - 1),
            f32(unit(rng.normal(size=(slots, heads, d))) * d ** -0.5),
            f32(unit(rng.normal(size=(slots, heads, d)))),
            f32(rng.normal(size=(slots, heads, d))),
            f32(-0.1 * rng.random(size=(slots, heads, d))),
            f32(2 * rng.random(size=(slots, heads))),
            jnp.asarray(at == 1), jnp.asarray(at % 8 != 7))


def probe_ssm_state_inputs(layers, slots, heads, p, n, groups):
    """state [L,B,H,P,N] f32, layer, x [B,H,P], dt [B,H], a_head [H], b, c
    [B,G,N], d [H], fresh, alive [B] (every eighth slot idle, one starting
    afresh)."""
    import jax
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(0)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    at = np.arange(slots)
    return (jax.random.normal(jax.random.PRNGKey(0),
                              (layers, slots, heads, p, n), jnp.float32),
            jnp.int32(layers - 1),
            f32(rng.normal(size=(slots, heads, p))),
            f32(0.1 * rng.random(size=(slots, heads))),
            f32(-rng.uniform(1, 16, size=heads)),
            f32(rng.normal(size=(slots, groups, n))),
            f32(rng.normal(size=(slots, groups, n))),
            f32(rng.normal(size=heads)),
            jnp.asarray(at == 1), jnp.asarray(at % 8 != 7))


def probe_selective_step_inputs(layers, slots, n, rows):
    """state [L,B,N,R,128] f32, layer, x, dt [B,R,128], a [N,R,128], b, c
    [B,N], fresh, alive [B] (every eighth slot idle, one starting afresh)."""
    import jax
    import jax.numpy as jnp

    np = _np()
    at = np.arange(slots)
    vectors = _selective_vectors(np.random.default_rng(0), (slots,), n, rows)
    return (jax.random.normal(jax.random.PRNGKey(0),
                              (layers, slots, n, rows, 128), jnp.float32),
            jnp.int32(layers - 1),
            *(jnp.asarray(v, jnp.float32) for v in vectors),
            jnp.asarray(at == 1), jnp.asarray(at % 8 != 7))


def probe_selective_scan_inputs(layers, slots, n, rows, s):
    """state [L,slots,N,R,128] f32, layer, the row's slot [1], x, dt
    [1,S,R,128], a [N,R,128], b, c [1,S,N], fresh [1]: one request's chunk
    of ``s`` tokens into the last slot, its last eighth padding."""
    import jax
    import jax.numpy as jnp

    np = _np()
    x, dt, a, b, c = _selective_vectors(
        np.random.default_rng(0), (1, s), n, rows)
    dt[:, s - s // 8:] = 0.0
    return (jax.random.normal(jax.random.PRNGKey(0),
                              (layers, slots, n, rows, 128), jnp.float32),
            jnp.int32(layers - 1), jnp.asarray([slots - 1], jnp.int32),
            *(jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c)),
            jnp.asarray([False]))


def probe_grouped_matmul_inputs(m, layers, e, k, n, rows):
    """xs [m, K], w [L·E, K, N] bf16, group_sizes [E] (``rows`` of the m
    spread over the experts, some with none), the last layer's first group."""
    import jax
    import jax.numpy as jnp

    np = _np()
    rng = np.random.default_rng(0)
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    sizes = np.bincount(rng.integers(0, e, rows), minlength=e)
    return (jax.random.normal(kx, (m, k), jnp.bfloat16),
            jax.random.normal(kw, (layers * e, k, n), jnp.bfloat16)
            * k ** -0.5,
            jnp.asarray(sizes, jnp.int32), jnp.int32((layers - 1) * e))


_PROBE_BUILDERS = {
    "grouped_expert_matmul": probe_grouped_matmul_inputs,
    "linear_state_update": probe_linear_state_inputs,
    "ssm_state_update": probe_ssm_state_inputs,
    "selective_state_update": probe_selective_step_inputs,
    "selective_state_scan": probe_selective_scan_inputs,
    "mla_sparse_prefill_masked": probe_mla_masked_inputs,
    "dsa_index_scores": probe_dsa_index_inputs,
    "mla_sparse_attention": probe_mla_sparse_inputs,
    "latent_cache_dma": probe_latent_dma_inputs,
    "paged_decode_attention_mq": probe_decode_inputs,
    "paged_prefill_attention": probe_prefill_inputs,
    "ragged_paged_prefill_attention": probe_ragged_inputs,
    "int8_matmul": probe_int8_matmul_inputs,
}
