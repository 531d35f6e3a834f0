"""Pallas TPU kernel: updater-role value write-back (paper §3.5 updater path).

assign / assign_add are the *non-structural* write role: they touch value
rows in place, never bucket structure.  On TPU this is a row-indexed
read-modify-write over the packed value plane (``core.table``): per
masked-in lane, DMA the row's 128-lane line into VMEM, merge the update
into the slot's lanes, DMA it back — the plane aliased input->output so
only touched rows move through VMEM.  Each write retires before the next
read, because distinct slots share a line.  Masked-out lanes touch
nothing.

PRECONDITION (enforced by callers, asserted in tests): the masked row ids
are unique within a batch.  The merge/assign paths dedupe before calling —
the same invariant the paper's updater kernels get from their
one-warp-per-key assignment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bucket_rows as br
from repro.kernels.find_scan import slot_lanes


def _scatter_kernel(add, width, q_tile, rows_ref, mask_ref, upd_ref, v_hbm,
                    out_hbm, vbuf, rsem, wsem):
    del v_hbm  # aliased with out_hbm
    i = pl.program_id(0)
    lanes = out_hbm.shape[1]

    def body(q, carry):
        n = i * q_tile + q

        @pl.when(mask_ref[n] != 0)
        def _():
            prow, lane0 = slot_lanes(rows_ref[n], width, lanes)
            rd = pltpu.make_async_copy(out_hbm.at[pl.ds(prow, 1), :], vbuf, rsem)
            rd.start()
            rd.wait()
            old = vbuf[...]
            upd = upd_ref[pl.ds(q, 1), :]
            if lanes != width:
                upd = pltpu.roll(upd, lane0, 1)
            lane = br.lane_iota(lanes)
            mine = (lane >= lane0) & (lane < lane0 + width)
            vbuf[...] = jnp.where(mine, old + upd if add else upd, old)
            wr = pltpu.make_async_copy(vbuf, out_hbm.at[pl.ds(prow, 1), :], wsem)
            wr.start()
            wr.wait()

        return carry

    jax.lax.fori_loop(0, q_tile, body, 0)


@functools.partial(jax.jit, static_argnames=("add", "q_tile", "interpret"))
def scatter_rows(values, rows, updates, mask, *, add: bool, q_tile: int,
                 interpret: bool):
    """Logical row rows[i] of the packed plane `values` = (itself +)?
    updates[i] where mask[i] (N a multiple of q_tile — the wrapper pads)."""
    n, width = updates.shape
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    lanes = values.shape[1]
    updates = jnp.pad(updates.astype(values.dtype),
                      ((0, 0), (0, lanes - width)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // q_tile,),
        in_specs=[pl.BlockSpec((q_tile, lanes), lambda i, *_: (i, 0)),
                  br.hbm()],
        out_specs=br.hbm(),
        scratch_shapes=[pltpu.VMEM((1, lanes), values.dtype),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        functools.partial(_scatter_kernel, add, width, q_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(values.shape, values.dtype),
        input_output_aliases={3: 0},  # values plane updated in place
        interpret=interpret,
        name="hkv_scatter_rows",
    )(rows, mask, updates, values)
