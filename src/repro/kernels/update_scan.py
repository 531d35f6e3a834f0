"""Pallas TPU kernels for the FUSED updater path (paper §3.5, updater role).

PR 1 kernel-completed the inserter (``upsert_scan``) and PR 6 the reader
(``find_scan``); the updater — the gradient path that dominates continuous
training — still ran as a three-launch composition: ``find_ptr`` locate,
``gather_rows`` value fetch, host-jnp optimizer math, ``scatter_rows``
write-back.  Every embedding row crossed HBM *twice* with a full kernel
boundary in between.  This module folds all four stages into ONE
scalar-prefetched pass per (deduped) query:

  1. probe + confirm      both candidate bucket rows stream in as
                          scalar-prefetch-indexed blocks and are matched
                          with the shared ``core.find.match_lanes`` oracle
                          (digest conjoined iff ``use_digest``) — the same
                          formula as ``find_scan`` and the jnp reference,
                          so kernel and oracle cannot fork;
  2. dual-bucket merge    hit1-wins-over-hit2, ``core.find.locate``'s merge;
  3. row RMW              an in-kernel HBM->VMEM DMA of the full value row
                          ``[dim + aux]`` at ``bucket * S + slot``, the
                          sparse optimizer applied *in-kernel* (static
                          variant per ``SparseOptimizer.name`` — the exact
                          ``SparseOptimizer.apply`` math on a [1, V] row
                          slice, so per-row equals batch application
                          bitwise), then a VMEM->HBM DMA back.

Mask domination (cache semantics — rejected embeddings do not train):

  * miss lanes resolve to row ``b1*S + 0`` (a valid address), read it, and
    write the freshly-read bytes back unchanged — the optimizer result is
    ``jnp.where``-selected away before the write DMA, so an un-admitted
    key never perturbs a resident row;
  * a ``qvalid`` lane gates the match IN-KERNEL: an EMPTY-padded query key
    would otherwise *match* an empty slot (empty slots store the all-ones
    sentinel in their key planes).  The find path can re-mask after the
    kernel because it only reads; an updater writes, so the gate must
    dominate the store inside the kernel.

Write-after-read ordering: each query's value RMW is fully serialized
(read.wait before apply, write.wait before the next query's read) because
miss lanes alias row ``b1*S+0`` and distinct slots share a 128-lane row
of the packed value plane.  The pipeline variant keeps its two-slot
metadata double buffer — query q+1's bucket rows stream while query q's
row is read-modified-written — so the latency hiding lives where the
traffic is (metadata), and the serialized value row is the correctness
anchor.  The optimizer runs on the plane row rotated so the slot's lanes
come first (``SparseOptimizer.apply_lanes``) and the result is rotated
back and lane-masked into the row.

PRECONDITION (enforced by callers, asserted in tests): query keys are
unique within a batch (the embedding layer dedupes and segment-sums
gradients first) — the same one-warp-per-key invariant as the paper's
update kernels.

Both variants compute exactly ``ref.update_scan_ref`` and are swept
against it in tests/test_update_kernel.py (interpret mode executes the
kernel bodies on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.find import match_lanes
from repro.kernels import bucket_rows as br
from repro.kernels.find_scan import _merge_hits, slot_lanes, to_front


def _kernel(opt, dim, width, depth, q_tile, group, use_digest,
            b1_ref, b2_ref, qd_ref, qh_ref, ql_ref, qv_ref,
            td, th, tl, g_ref, v_hbm, found_ref, out_hbm,
            d1, h1, l1, d2, h2, l2, vbuf, sems, rsem, wsem):
    del v_hbm  # aliased with out_hbm — all row traffic goes through out_hbm
    i = pl.program_id(0)
    lanes = out_hbm.shape[1]
    planes = (td, th, tl)

    def copies(q, s):
        n = i * q_tile + q
        cps = []
        for k, (b, bufs) in enumerate(((b1_ref[n], (d1, h1, l1)),
                                       (b2_ref[n], (d2, h2, l2)))):
            for j, (p, buf) in enumerate(zip(planes, bufs)):
                cps.append(br.row_copy(p, buf.at[s], sems.at[s, 3 * k + j],
                                       b, group if j == 0 else 1))
        return cps

    def body(q, s):
        n = i * q_tile + q
        qh = qh_ref[n].astype(jnp.uint32)
        ql = ql_ref[n].astype(jnp.uint32)
        qd = qd_ref[n].astype(jnp.uint32)

        def row_match(b, d, h, l):
            if use_digest:
                m = match_lanes(h[s], l[s], qh, ql, br.digest_row(d[s], b), qd)
            else:
                m = match_lanes(h[s], l[s], qh, ql)
            return br.first_lane(m)

        b1, b2 = b1_ref[n], b2_ref[n]
        hit1, slot1 = row_match(b1, d1, h1, l1)
        hit2, slot2 = row_match(b2, d2, h2, l2)
        found, sel, slot = _merge_hits(hit1, slot1, hit2, slot2)
        # qvalid gate must dominate the store: an EMPTY-padded query
        # matches empty slots (both are the all-ones key sentinel) and
        # would otherwise train a vacant row.
        found = found & (qv_ref[n] != 0)
        found_ref[n] = found.astype(jnp.int32)

        row = jnp.where(sel == 0, b1, b2) * th.shape[1] + slot
        prow, lane0 = slot_lanes(row, width, lanes)
        # serialized row RMW: read.wait -> apply -> masked write -> write.wait
        rd = pltpu.make_async_copy(out_hbm.at[pl.ds(prow, 1), :], vbuf, rsem)
        rd.start()
        rd.wait()
        raw = vbuf[...]
        roll = lambda v, k: pltpu.roll(v, k % lanes, 1)
        new = opt.apply_lanes(to_front(raw, lane0, lanes, width),
                              g_ref[pl.ds(q, 1), :], dim, roll)
        if lanes != width:
            new = roll(new, lane0)
        lane = br.lane_iota(lanes)
        mine = found & (lane >= lane0) & (lane < lane0 + width)
        vbuf[...] = jnp.where(mine, new.astype(raw.dtype), raw)
        wr = pltpu.make_async_copy(vbuf, out_hbm.at[pl.ds(prow, 1), :], wsem)
        wr.start()
        wr.wait()

    br.query_loop(q_tile, depth, copies, body)


@functools.partial(jax.jit, static_argnames=(
    "opt", "dim", "width", "variant", "q_tile", "use_digest", "interpret"))
def update_scan(tdigests, tkey_hi, tkey_lo, tvalues,
                bucket1, bucket2, qdigest, qkey_hi, qkey_lo, qvalid,
                grads, *, opt, dim: int, width: int, variant: str,
                q_tile: int, use_digest: bool, interpret: bool):
    """Fused update over a tile-padded batch.

    tvalues (the packed value plane, logical row width `width`) is updated
    IN PLACE (input/output aliased).  Returns (found i32 [N], new_values):
      found       1 iff the key matched a live slot AND qvalid[i] != 0
      new_values  the plane with each hit row replaced by
                  ``opt.apply(row, grads[i], dim)``; miss/invalid lanes
                  leave their (aliased) rows bit-identical.

    ``opt`` is a static ``SparseOptimizer`` (frozen dataclass — hashable);
    its variant is compiled into the kernel body, not branched at runtime.
    Single-bucket mode: pass bucket2 == bucket1.
    """
    n = bucket1.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    nb, s = tdigests.shape
    lanes = tvalues.shape[1]
    group = br.digest_group(nb)
    grads = jnp.pad(grads.astype(tvalues.dtype),
                    ((0, 0), (0, lanes - grads.shape[1])))
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    out_spec, out_shape = br.smem_out(n)
    meta = lambda dt, rows=1: pltpu.VMEM((2, rows, s), dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n // q_tile,),
        in_specs=[br.hbm()] * 3 + [
            pl.BlockSpec((q_tile, lanes), lambda i, *_: (i, 0)),
            br.hbm()],
        out_specs=[out_spec, br.hbm()],
        scratch_shapes=(
            [meta(jnp.uint8, group), meta(jnp.uint32), meta(jnp.uint32)] * 2
            + [pltpu.VMEM((1, lanes), tvalues.dtype),
               pltpu.SemaphoreType.DMA((2, 6)),
               pltpu.SemaphoreType.DMA,    # value read semaphore
               pltpu.SemaphoreType.DMA]),  # value write semaphore
    )
    return pl.pallas_call(
        functools.partial(_kernel, opt, dim, width, br.variant_depth(variant),
                          q_tile, group, use_digest),
        grid_spec=grid_spec,
        out_shape=[out_shape,
                   jax.ShapeDtypeStruct(tvalues.shape, tvalues.dtype)],
        input_output_aliases={10: 1},  # value plane updated in place
        interpret=interpret,
        name=f"hkv_update_scan_{variant}",
    )(bucket1, bucket2, i32(qdigest), i32(qkey_hi), i32(qkey_lo),
      i32(qvalid), tdigests, tkey_hi, tkey_lo, grads, tvalues)
