"""Deliberately broken Pallas kernels — one contract violation each.

``tests/test_analysis.py`` traces these through
``kernel_contracts.check_traced_kernel`` and asserts the matching finding
rule fires.  Shapes are tiny; the kernels are traced, never executed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


N, S, V = 4, 8, 8
RING = 4          # slots of the ring-buffer fixture


def _unpaired_dma_kernel(rows_ref, v_hbm, out_ref, vbuf, vsem):
    i = pl.program_id(0)
    row = rows_ref[i]
    cp = pltpu.make_async_copy(v_hbm.at[pl.ds(row, 1), :], vbuf, vsem)
    cp.start()
    # BUG: no cp.wait() — the copy is never retired before vbuf is read
    out_ref[0, :] = jnp.where(row >= 0, vbuf[0, :], jnp.zeros_like(vbuf[0, :]))


def unpaired_dma(values, rows, *, interpret: bool = True):
    n = rows.shape[0]
    v = values.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        out_specs=pl.BlockSpec((1, v), lambda i, r: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, v), values.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        _unpaired_dma_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, v), values.dtype),
        interpret=interpret,
        name="fixture_unpaired_dma",
    )(rows, values)


def _unpaired_ring_dma_kernel(rows_ref, v_hbm, out_ref, ring, sems):
    i = pl.program_id(0)
    row = rows_ref[i]
    slot = jax.lax.rem(i, RING)
    # BUG: the ring slot's copy is started to be retired later, and no
    # later wait on its semaphore exists
    pltpu.make_async_copy(v_hbm.at[pl.ds(row, 1), :], ring.at[slot],
                          sems.at[slot]).start()
    out_ref[0, :] = jnp.where(row >= 0, ring[slot][0, :],
                              jnp.zeros_like(ring[slot][0, :]))


def unpaired_ring_dma(values, rows, *, interpret: bool = True):
    n = rows.shape[0]
    v = values.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        out_specs=pl.BlockSpec((1, v), lambda i, r: (i, 0)),
        scratch_shapes=[pltpu.VMEM((RING, 1, v), values.dtype),
                        pltpu.SemaphoreType.DMA((RING,))],
    )
    return pl.pallas_call(
        _unpaired_ring_dma_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, v), values.dtype),
        interpret=interpret,
        name="fixture_unpaired_ring_dma",
    )(rows, values)


def _unmasked_store_kernel(mask_ref, val_ref, out_ref):
    i = pl.program_id(0)
    # BUG: float output store ignores the mask — misses keep stale lanes
    out_ref[0, :] = val_ref[0, :] * jnp.float32(2.0)


def unmasked_store(values, mask, *, interpret: bool = True):
    n, v = values.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, v), lambda i, m: (i, 0))],
        out_specs=pl.BlockSpec((1, v), lambda i, m: (i, 0)),
    )
    return pl.pallas_call(
        _unmasked_store_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, v), values.dtype),
        interpret=interpret,
        name="fixture_unmasked_store",
    )(mask, values)


def _direct_hbm_kernel(rows_ref, v_hbm, out_ref):
    i = pl.program_id(0)
    # BUG: direct vector load from an ANY/HBM-space ref (no async copy)
    row = jnp.where(rows_ref[i] >= 0, v_hbm[0, :], v_hbm[0, :])
    out_ref[0, :] = jnp.where(rows_ref[i] >= 0, row, jnp.zeros_like(row))


def direct_hbm_read(values, rows, *, interpret: bool = True):
    n = rows.shape[0]
    v = values.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
        out_specs=pl.BlockSpec((1, v), lambda i, r: (i, 0)),
    )
    return pl.pallas_call(
        _direct_hbm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, v), values.dtype),
        interpret=interpret,
        name="fixture_direct_hbm",
    )(rows, values)


def _args():
    return (jnp.zeros((N * S, V), jnp.float32),
            jnp.zeros((N,), jnp.int32))


def trace_unpaired_dma():
    vals, rows = _args()
    return jax.make_jaxpr(functools.partial(unpaired_dma))(vals, rows)


def trace_unpaired_ring_dma():
    vals, rows = _args()
    return jax.make_jaxpr(functools.partial(unpaired_ring_dma))(vals, rows)


def trace_unmasked_store():
    vals, rows = _args()
    return jax.make_jaxpr(functools.partial(unmasked_store))(
        jnp.zeros((N, V), jnp.float32), rows)


def trace_direct_hbm():
    vals, rows = _args()
    return jax.make_jaxpr(functools.partial(direct_hbm_read))(vals, rows)
