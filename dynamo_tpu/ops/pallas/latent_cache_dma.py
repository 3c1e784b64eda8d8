"""Row writes and block reads of the latent cache, as DMAs and nothing else.

The latent cache (ops/latent_cache.py) is [.., 1, W] uint32 so that one row
is a legal DMA for the sparse attention kernel.  XLA's own scatter and gather
prefer another tiling of that array and re-lay the WHOLE cache out around
them (compiled for a described v5e: two copies of 3.5 GB a decode step, and a
long prefill chunk did not fit the chip).  So on the TPU the cache is touched
by kernels only:

``write_rows``     rows [T, 1, W] -> cache rows ``slots`` [T] (a negative slot
                   writes nothing); the cache is donated and aliased.
``gather_blocks``  cache blocks ``ids`` [n] -> [n, Bs, 1, W].

One DMA a row or block, HBM to HBM, all started before any is awaited.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["write_rows", "gather_blocks"]


def _copy_each(n, src_of, dst_of, wanted, sem):
    """Start copy i for every wanted i in [0, n), then wait for each."""
    def copy(i):
        return pltpu.make_async_copy(src_of(i), dst_of(i), sem)

    def start(i, _):
        @pl.when(wanted(i))
        def _():
            copy(i).start()
        return 0

    def wait(i, _):
        @pl.when(wanted(i))
        def _():
            copy(i).wait()
        return 0

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def _write_kernel(slots_ref, rows_hbm, cache_in, cache_out, sem):
    del cache_in        # aliased to cache_out
    _copy_each(
        rows_hbm.shape[0],
        lambda i: rows_hbm.at[pl.ds(i, 1)],
        lambda i: cache_out.at[pl.ds(jnp.maximum(slots_ref[i], 0), 1)],
        lambda i: slots_ref[i] >= 0, sem)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def write_rows(cache: jax.Array, rows: jax.Array, slots: jax.Array,
               interpret: bool = False) -> jax.Array:
    """cache [R, 1, W] with rows [T, 1, W] written at ``slots`` [T]."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_space, any_space], out_specs=any_space,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name="latent_cache_write_rows",
    )(slots.astype(jnp.int32), rows.astype(cache.dtype), cache)


def _gather_kernel(ids_ref, cache_hbm, out_hbm, sem):
    _copy_each(
        out_hbm.shape[0],
        lambda i: cache_hbm.at[pl.ds(ids_ref[i], 1)],
        lambda i: out_hbm.at[pl.ds(i, 1)],
        lambda i: True, sem)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(cache: jax.Array, ids: jax.Array,
                  interpret: bool = False) -> jax.Array:
    """Blocks ``ids`` [n] of cache [Nb, Bs, 1, W] -> [n, Bs, 1, W]."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_space], out_specs=any_space,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(
            (ids.shape[0], *cache.shape[1:]), cache.dtype),
        interpret=interpret,
        name="latent_cache_gather_blocks",
    )(ids.astype(jnp.int32), cache)
