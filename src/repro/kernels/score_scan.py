"""Pallas TPU kernel: per-bucket score scan (paper §3.3 eviction scan).

The insert path's victim search scans all 128 scores of a bucket for the
minimum (Alg. 2 line 11).  On GPU that's a warp-cooperative cg::reduce; on
TPU it is a lane-dimension reduction over VMEM-tiled bucket rows.  This
kernel computes, for a tile of buckets at once:

  occupancy[b]           live-entry count (drives dual-bucket phase D1)
  min_score hi/lo [b]    lexicographic 64-bit min over live slots (D2 +
                         admission threshold)
  argmin[b]              victim slot

It is a straight tiled reduction — no dynamic indexing — so it also serves
as the package's reference Pallas pattern for plain VMEM BlockSpec tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.find import match_lanes
from repro.core.u64 import empty_lanes
from repro.kernels.bucket_rows import ordered


def _stats_kernel(kh_ref, kl_ref, sh_ref, sl_ref, occ_ref, mh_ref, ml_ref, am_ref):
    # uint32 scores are reduced in `ordered` int32 form (the chip reduces
    # no unsigned integers); flipping the sign bit back restores them
    top = jnp.iinfo(jnp.int32).max
    occ_mask = ~empty_lanes(kh_ref[...], kl_ref[...])
    occ_ref[...] = jnp.sum(occ_mask.astype(jnp.int32), axis=1, keepdims=True)
    shi = jnp.where(occ_mask, ordered(sh_ref[...]), top)
    slo = jnp.where(occ_mask, ordered(sl_ref[...]), top)
    min_hi = jnp.min(shi, axis=1, keepdims=True)
    min_lo = jnp.min(jnp.where(shi == min_hi, slo, top), axis=1, keepdims=True)
    mh_ref[...] = min_hi
    ml_ref[...] = min_lo
    is_min = match_lanes(shi, slo, min_hi, min_lo)
    lane = jax.lax.broadcasted_iota(jnp.int32, is_min.shape, 1)
    first = jnp.min(jnp.where(is_min, lane, is_min.shape[1]), axis=1,
                    keepdims=True)
    am_ref[...] = jnp.where(first < is_min.shape[1], first, 0)


@functools.partial(jax.jit, static_argnames=("bucket_tile", "interpret"))
def bucket_stats(tkey_hi, tkey_lo, score_hi, score_lo, *, bucket_tile: int,
                 interpret: bool):
    """Per-bucket (occ, min_hi, min_lo, argmin) over the whole table.

    bucket_tile=8 keeps each block at the natural (8, 128) vreg shape:
    4 planes x 8x128 x 4 B = 16 KB of VMEM per step.
    """
    b, s = tkey_hi.shape
    assert b % bucket_tile == 0, "wrapper pads bucket count"
    grid = (b // bucket_tile,)
    in_spec = pl.BlockSpec((bucket_tile, s), lambda i: (i, 0))
    out_spec = pl.BlockSpec((bucket_tile, 1), lambda i: (i, 0))
    occ, mh, ml, am = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="hkv_bucket_stats",
    )(tkey_hi, tkey_lo, score_hi, score_lo)
    unorder = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32) ^ jnp.uint32(0x80000000)
    return occ[:, 0], unorder(mh[:, 0]), unorder(ml[:, 0]), am[:, 0]
