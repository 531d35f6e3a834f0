"""Pallas TPU kernels for the FUSED find path (paper §4.3 + §3.6 in one pass).

PRs 1–5 kernel-completed the inserter (``upsert_scan``) and sweep
(``sweep_scan``) paths, but the reader still ran as a two-launch pair:
``digest_scan`` locate (one launch per candidate bucket) followed by a
position-addressed ``gather_rows`` value pass — re-deriving the row address
on-host between launches and paying a second grid's worth of latency.  The
paper's find kernel does not: a warp walks the digest line, confirms the
full key, and ``__pipeline_memcpy_async``-copies the value slice before it
retires the query.  This module is that kernel for TPU.

One scalar-prefetched pass per query over BOTH candidate bucket rows:

  1. digest pre-filter    one uint8 lane-row compare per candidate bucket
                          (the 128 B cache-line transaction of §3.2);
  2. full-key confirm     the same match formula as the jnp reference
                          ``core.find._match_in_bucket`` — key planes
                          compared, digest conjoined iff ``use_digest``
                          (shared-formula bit-parity, the sweep_scan rule);
  3. dual-bucket merge    hit1-wins-over-hit2, exactly
                          ``core.find.locate``'s merge;
  4. score readout        the hit slot's (score_hi, score_lo) lifted from
                          the streamed metadata rows, so ``FindResult`` /
                          ``FindRowsResult`` scores need no second probe;
  5. value gather         a data-dependent HBM->VMEM ``make_async_copy``
                          of the hit row at ``bucket * S + slot``.  The
                          row index exists only *inside* the kernel (it is
                          the match result), which is precisely why the
                          unfused path needed a second launch: BlockSpec
                          index maps cannot depend on in-kernel values,
                          but an explicit DMA can.

Two variants over the same tile of queries per grid step
(``bucket_rows.query_loop``), differing only in how many queries' DMAs
are in flight: ``tlp`` fetches, waits and compares one query at a time.
``pipeline`` streams query q+1's ten metadata rows (5 planes x 2 buckets)
while query q is compared, and starts query q's value-row DMA into slot
q mod 2 of a VMEM ring without waiting: query q is retired in query
q+1's step (wait, rotate, mask, store its row), and the tile's last query
after the loop.  No query waits on its own value row.  A retired query's
lane and hit flag are rebuilt from its SMEM outputs.  The value row is
one 128-lane row of the packed value plane (``core.table``), rotated in
VMEM so the slot's lanes come first.

Both compute exactly ``ref.find_scan_ref`` and are swept against it and
against the jnp ``core.find`` oracle in tests/test_find_kernel.py
(interpret mode executes the kernel bodies on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.find import match_lanes
from repro.core.table import value_pitch
from repro.kernels import bucket_rows as br


def _merge_hits(hit1, slot1, hit2, slot2):
    """Shared dual-bucket merge: (found, sel, slot) from per-bucket hits —
    the exact `core.find.locate` merge (hit1 wins, miss defaults to b1)."""
    found = hit1 | hit2
    sel = jnp.where(hit1, 0, jnp.where(hit2, 1, 0)).astype(jnp.int32)
    slot = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
    return found, sel, slot


def slot_lanes(row, width: int, lanes: int):
    """(plane row, first lane) of logical value row `row` in the packed
    plane (`core.table.value_coords`, scalar form)."""
    per = lanes // value_pitch(width)
    return row // per, (row % per) * value_pitch(width)


def to_front(x, lane0, lanes: int, width: int):
    """Rotate a [1, L] plane row so lanes [lane0, lane0 + width) come
    first (no-op where one slot fills the row)."""
    if lanes == value_pitch(width):
        return x
    return pltpu.roll(x, (lanes - lane0) % lanes, 1)


def _kernel(depth, q_tile, group, width, use_digest,
            b1_ref, b2_ref, qd_ref, qh_ref, ql_ref,
            td, th, tl, tsh, tsl, tv,
            found_ref, sel_ref, slot_ref, shi_ref, slo_ref, val_ref,
            d1, h1, l1, sh1, sl1, d2, h2, l2, sh2, sl2, vbuf, sems, vsems):
    i = pl.program_id(0)
    lanes = tv.shape[1]
    slots = th.shape[1]
    planes = (td, th, tl, tsh, tsl)
    # The value ring has the metadata ring's depth: query q's row is waited
    # on `lag` queries after its start.  On a v5e at config A, deeper rings
    # gained nothing: every (metadata, value) depth in {2, 4, 8} x
    # {2, 4, 8, 16} ran within 0.2 % of (2, 2) or slower (PERF.md, Findings),
    # since the compare, not a DMA, is now the longer part of a query.
    lag = depth - 1

    def copies(q, s):
        n = i * q_tile + q
        cps = []
        for k, (b, bufs) in enumerate(((b1_ref[n], (d1, h1, l1, sh1, sl1)),
                                       (b2_ref[n], (d2, h2, l2, sh2, sl2)))):
            for j, (p, buf) in enumerate(zip(planes, bufs)):
                cps.append(br.row_copy(p, buf.at[s], sems.at[s, 5 * k + j],
                                       b, group if j == 0 else 1))
        return cps

    def value_copy(q):
        """Query q's value-row DMA into ring slot q mod `depth`, and the
        slot's first lane, rebuilt from the query's SMEM outputs.  Misses
        fetch row b1*S+0 (a valid address); `retire` masks them to zeros,
        the same contract as `find.gather_values`."""
        n = i * q_tile + q
        row = jnp.where(sel_ref[n] == 0, b1_ref[n], b2_ref[n]) * slots \
            + slot_ref[n]
        prow, lane0 = slot_lanes(row, width, lanes)
        v = jax.lax.rem(q, depth)
        return (pltpu.make_async_copy(tv.at[pl.ds(prow, 1), :], vbuf.at[v],
                                      vsems.at[v]), lane0, v)

    def retire(q):
        cp, lane0, v = value_copy(q)
        cp.wait()
        x = to_front(vbuf[v], lane0, lanes, width)[:, :width]
        found = found_ref[i * q_tile + q] != 0
        val_ref[pl.ds(q, 1), :] = jnp.where(found, x, jnp.zeros_like(x))

    def body(q, s):
        n = i * q_tile + q
        qh = qh_ref[n].astype(jnp.uint32)
        ql = ql_ref[n].astype(jnp.uint32)
        qd = qd_ref[n].astype(jnp.uint32)

        def row_match(b, d, h, l):
            # full-key compare, gated by the one-lane-row digest pre-filter
            # — the shared `core.find.match_lanes` oracle, so kernel and
            # jnp reference cannot fork
            if use_digest:
                m = match_lanes(h[s], l[s], qh, ql, br.digest_row(d[s], b), qd)
            else:
                m = match_lanes(h[s], l[s], qh, ql)
            return br.first_lane(m)

        hit1, slot1 = row_match(b1_ref[n], d1, h1, l1)
        hit2, slot2 = row_match(b2_ref[n], d2, h2, l2)
        found, sel, slot = _merge_hits(hit1, slot1, hit2, slot2)

        # score readout from the already-streamed metadata rows
        lane = br.lane_iota(slots) == slot
        pick = lambda a, b: br.pick(jnp.where(sel == 0, a[s], b[s]), lane)
        found_ref[n] = found.astype(jnp.int32)
        sel_ref[n] = sel
        slot_ref[n] = slot
        shi_ref[n] = jnp.where(found, pick(sh1, sh2), 0)
        slo_ref[n] = jnp.where(found, pick(sl1, sl2), 0)

        # value gather: position addressing (§3.6) resolved in-kernel; the
        # row DMA is waited on `lag` queries later
        value_copy(q)[0].start()
        if lag == 0:
            retire(q)
        else:
            @pl.when(q >= lag)
            def _():
                retire(q - lag)

    br.query_loop(q_tile, depth, copies, body)
    for q in range(max(0, q_tile - lag), q_tile):
        retire(q)


@functools.partial(jax.jit, static_argnames=(
    "width", "variant", "q_tile", "use_digest", "interpret"))
def find_scan(tdigests, tkey_hi, tkey_lo, tscore_hi, tscore_lo, tvalues,
              bucket1, bucket2, qdigest, qkey_hi, qkey_lo, *, width: int,
              variant: str, q_tile: int, use_digest: bool, interpret: bool):
    """Fused find over a tile-padded batch.

    Returns (found, sel, slot, score_hi, score_lo, values):
      found     int32 [N] — 1 iff the key matched in either candidate bucket
      sel       int32 [N] — 0 = bucket1 holds it (or miss), 1 = bucket2
      slot      int32 [N] — matching slot (0 on miss)
      score_hi  uint32 [N] — hit entry's score planes (0 on miss)
      score_lo  uint32 [N]
      values    [N, width] — the hit's logical value row (zeros on miss)

    `tvalues` is the packed value plane; `width` its logical row width.
    Single-bucket mode: pass bucket2 == bucket1 (sel collapses to 0).
    """
    n = bucket1.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    nb, s = tdigests.shape
    lanes = tvalues.shape[1]
    group = br.digest_group(nb)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    out_spec, out_shape = br.smem_out(n)
    depth = br.variant_depth(variant)
    meta = lambda dt, rows=1: pltpu.VMEM((depth, rows, s), dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // q_tile,),
        in_specs=[br.hbm()] * 6,
        out_specs=[out_spec] * 5 + [
            pl.BlockSpec((q_tile, width), lambda i, *_: (i, 0))],
        scratch_shapes=(
            [meta(jnp.uint8, group)] + [meta(jnp.uint32)] * 4
            + [meta(jnp.uint8, group)] + [meta(jnp.uint32)] * 4
            + [pltpu.VMEM((depth, 1, lanes), tvalues.dtype),
               pltpu.SemaphoreType.DMA((depth, 10)),
               pltpu.SemaphoreType.DMA((depth,))]),
    )
    found, sel, slot, shi, slo, vals = pl.pallas_call(
        functools.partial(_kernel, depth, q_tile, group, width, use_digest),
        grid_spec=grid_spec,
        out_shape=[out_shape] * 5 + [
            jax.ShapeDtypeStruct((n, width), tvalues.dtype)],
        interpret=interpret,
        name=f"hkv_find_scan_{variant}",
    )(bucket1, bucket2, i32(qdigest), i32(qkey_hi), i32(qkey_lo),
      tdigests, tkey_hi, tkey_lo, tscore_hi, tscore_lo, tvalues)
    u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    return found, sel, slot, u32(shi), u32(slo), vals
