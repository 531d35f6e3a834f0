"""Pallas TPU kernel: position-addressed value gather (paper §3.6, §4.3 TLPv2).

After the digest scan resolves (bucket, slot) -> row = bucket*S + slot, the
value copy is a pure bandwidth problem.  The paper's TLPv2 regroups threads
into cooperative value-copy gangs; the TPU analogue walks a tile of
scalar-prefetched row ids per grid step and DMAs each row's 128-lane line
of the packed value plane (``core.table``) into VMEM, rotating the slot's
lanes to the front.

Rows with mask==0 (misses) produce zero rows, matching `find`'s contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bucket_rows as br
from repro.kernels.find_scan import slot_lanes, to_front


def _gather_kernel(width, q_tile, rows_ref, mask_ref, v_hbm, out_ref,
                   vbuf, sem):
    i = pl.program_id(0)
    lanes = v_hbm.shape[1]

    def body(q, carry):
        n = i * q_tile + q
        prow, lane0 = slot_lanes(rows_ref[n], width, lanes)
        cp = pltpu.make_async_copy(v_hbm.at[pl.ds(prow, 1), :], vbuf, sem)
        cp.start()
        cp.wait()
        x = to_front(vbuf[...], lane0, lanes, width)[:, :width]
        out_ref[pl.ds(q, 1), :] = jnp.where(mask_ref[n] != 0, x,
                                            jnp.zeros_like(x))
        return carry

    jax.lax.fori_loop(0, q_tile, body, 0)


@functools.partial(jax.jit, static_argnames=("width", "q_tile", "interpret"))
def gather_rows(values, rows, mask, *, width: int, q_tile: int,
                interpret: bool):
    """out[i] = mask[i] ? logical row rows[i] : 0, of the packed plane
    `values` (rows pre-clipped; N a multiple of q_tile — the wrapper pads)."""
    n = rows.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // q_tile,),
        in_specs=[br.hbm()],
        out_specs=pl.BlockSpec((q_tile, width), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, values.shape[1]), values.dtype),
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, width, q_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, width), values.dtype),
        interpret=interpret,
        name="hkv_gather_rows",
    )(rows, mask, values)
