"""Pallas TPU kernels for the digest-accelerated find path (paper §3.2, §4.3).

The GPU design: 128 one-byte digests fill one 128 B L1 cache line; a warp
scans them with 32 ``__vcmpeq4`` SIMD compares; only digest hits touch the
64-bit keys.  The TPU adaptation keeps the co-design but re-maps each level
of the hierarchy (DESIGN.md §2):

  GPU 128 B cache line  ->  one TPU vreg lane row: a bucket's 128 digests
                            occupy the 128-lane minor dimension of VMEM, so
                            one vector compare covers the entire candidate
                            set (the paper's "definitive miss in one
                            transaction" property).
  __vcmpeq4 SIMD scan   ->  a single int-eq over the lane dimension (VPU).
  __pipeline_memcpy_async-> explicit HBM->VMEM ``make_async_copy`` of the
                            query's bucket rows (``bucket_rows``).

Two variants, mirroring the paper's kernel-selection tiers, over the same
tile of queries per grid step (``bucket_rows.query_loop``):

  tlp       fetch, wait and compare one query at a time.
  pipeline  query q+1's bucket rows stream in while query q is compared
            (the latency-hiding structure of the paper's 4-stage
            warp-cooperative kernel, §4.3).

Both compute exactly ``ref.digest_scan_ref`` and are swept against it in
tests (interpret mode executes the kernel bodies on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.find import match_lanes
from repro.kernels import bucket_rows as br


def _kernel(depth, q_tile, group, b_ref, qd_ref, qh_ref, ql_ref,
            td, th, tl, slot_ref, found_ref, dbuf, hbuf, lbuf, sems):
    i = pl.program_id(0)

    def copies(q, s):
        b = b_ref[i * q_tile + q]
        return (br.row_copy(td, dbuf.at[s], sems.at[s, 0], b, group),
                br.row_copy(th, hbuf.at[s], sems.at[s, 1], b),
                br.row_copy(tl, lbuf.at[s], sems.at[s, 2], b))

    def body(q, s):
        n = i * q_tile + q
        # one vector compare over the 128-lane row = the whole candidate
        # set; the mask formula is the shared core.find.match_lanes oracle
        m = match_lanes(hbuf[s], lbuf[s],
                        qh_ref[n].astype(jnp.uint32),
                        ql_ref[n].astype(jnp.uint32),
                        br.digest_row(dbuf[s], b_ref[n]),
                        qd_ref[n].astype(jnp.uint32))
        hit, slot = br.first_lane(m)
        found_ref[n] = hit.astype(jnp.int32)
        slot_ref[n] = slot

    br.query_loop(q_tile, depth, copies, body)


@functools.partial(jax.jit, static_argnames=("variant", "q_tile", "interpret"))
def digest_scan(tdigests, tkey_hi, tkey_lo, buckets, qdigest, qkey_hi,
                qkey_lo, *, variant: str, q_tile: int, interpret: bool):
    """(slot int32 [N], found int32 [N]) of each query in its bucket.

    N must be a multiple of q_tile (the wrapper pads).  Per-query scalars
    (int32 or bitcast uint32) ride scalar prefetch into SMEM.
    """
    n = buckets.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    nb, s = tdigests.shape
    group = br.digest_group(nb)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    (out_spec, out_shape) = br.smem_out(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // q_tile,),
        in_specs=[br.hbm()] * 3,
        out_specs=[out_spec] * 2,
        scratch_shapes=[
            pltpu.VMEM((2, group, s), jnp.uint8),
            pltpu.VMEM((2, 1, s), jnp.uint32),
            pltpu.VMEM((2, 1, s), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, 3)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, br.variant_depth(variant), q_tile, group),
        grid_spec=grid_spec,
        out_shape=[out_shape] * 2,
        interpret=interpret,
        name=f"hkv_digest_scan_{variant}",
    )(buckets, i32(qdigest), i32(qkey_hi), i32(qkey_lo),
      tdigests, tkey_hi, tkey_lo)
