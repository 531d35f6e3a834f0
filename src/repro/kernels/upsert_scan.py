"""Pallas TPU kernels for the fused upsert/evict path (paper §3.3, Alg. 2/3).

The paper resolves a full-bucket upsert *in line*: one kernel pass performs
digest pre-filter -> full-key match -> empty-slot claim -> score-argmin
eviction (or admission rejection), with dual-bucket selection picking the
target bucket.  This module is the TPU inserter-side counterpart of
``digest_scan`` (the reader side): two per-query row-pass kernels that,
together with the shared batch-closure orchestration in ``core/merge.py``,
kernel-complete the hottest mutation path (DESIGN.md §4).

  upsert_probe  one fused pass over a query's candidate bucket row(s):
                digest pre-filter + full-key compare (Alg. 1), occupancy
                count, lexicographic min-score reduction (Alg. 2 line 11),
                and the dual-bucket two-phase D1/D2 selection (Alg. 3 /
                Fig. 5) — all computed from a single HBM->VMEM row fetch
                per candidate bucket, the same one-transaction property
                the GPU design gets from its 128 B digest cache line.
  claim_scan    rank-r victim extraction: for a miss with within-bucket
                canonical rank r, return the r-th weakest slot of its
                target bucket under the total victim order (empty-first,
                then ascending score / key / slot).  Computed branch-free
                via pairwise lexicographic ranking over the 128-lane row
                (a 128x128 VPU compare block), so every query is
                independent — no serialization, conflict-free claims.

Both walk a tile of queries per grid step, streaming query q+1's bucket
rows while query q is resolved (``bucket_rows.query_loop``).  They
execute in interpret mode off-TPU and are swept against the pure-jnp
stages in tests/test_upsert_kernel.py (bit-identical statuses, evicted
pairs, and post-state required).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.find import match_lanes
from repro.core.u64 import empty_lanes
from repro.kernels import bucket_rows as br

_PLANES = 5  # digest, key_hi, key_lo, score_hi, score_lo


def _lex_min(hi, lo):
    """Lexicographic u64 min of (hi, lo) lane rows, in `br.ordered` int32
    form (order-exact; only ever compared)."""
    min_hi = jnp.min(hi)
    min_lo = jnp.min(jnp.where(hi == min_hi, lo, jnp.iinfo(jnp.int32).max))
    return min_hi, min_lo


# =============================================================================
# upsert_probe: fused match + bucket-stats + dual-bucket selection
# =============================================================================


def _probe_kernel(q_tile, group, use_digest,
                  b1_ref, b2_ref, qd_ref, qh_ref, ql_ref,
                  td, th, tl, tsh, tsl,
                  found_ref, hitsel_ref, slot_ref, tgtsel_ref,
                  d1, h1, l1, sh1, sl1, d2, h2, l2, sh2, sl2, sems):
    i = pl.program_id(0)
    planes = (td, th, tl, tsh, tsl)
    slots = th.shape[1]

    def copies(q, s):
        n = i * q_tile + q
        cps = []
        for k, (b, bufs) in enumerate(((b1_ref[n], (d1, h1, l1, sh1, sl1)),
                                       (b2_ref[n], (d2, h2, l2, sh2, sl2)))):
            for j, (p, buf) in enumerate(zip(planes, bufs)):
                cps.append(br.row_copy(p, buf.at[s],
                                       sems.at[s, _PLANES * k + j],
                                       b, group if j == 0 else 1))
        return cps

    def body(q, s):
        n = i * q_tile + q
        qh = qh_ref[n].astype(jnp.uint32)
        ql = ql_ref[n].astype(jnp.uint32)
        qd = qd_ref[n].astype(jnp.uint32)

        def row_pass(b, d, h, l, sh, sl):
            hh, ll = h[s], l[s]
            # full-key compare, gated by the one-lane-row digest
            # pre-filter — the shared `core.find.match_lanes` oracle
            if use_digest:
                m = match_lanes(hh, ll, qh, ql, br.digest_row(d[s], b), qd)
            else:
                m = match_lanes(hh, ll, qh, ql)
            occ_mask = ~empty_lanes(hh, ll)
            # lexicographic u64 min over live slots (empties -> +inf)
            top = jnp.iinfo(jnp.int32).max
            min_hi, min_lo = _lex_min(
                jnp.where(occ_mask, br.ordered(sh[s]), top),
                jnp.where(occ_mask, br.ordered(sl[s]), top))
            hit, slot = br.first_lane(m)
            return hit, slot, jnp.sum(occ_mask.astype(jnp.int32)), min_hi, min_lo

        hit1, slot1, occ1, m1h, m1l = row_pass(b1_ref[n], d1, h1, l1, sh1, sl1)
        hit2, slot2, occ2, m2h, m2l = row_pass(b2_ref[n], d2, h2, l2, sh2, sl2)

        found_ref[n] = (hit1 | hit2).astype(jnp.int32)
        hitsel_ref[n] = jnp.where(hit1, 0, 1).astype(jnp.int32)
        slot_ref[n] = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
        # dual-bucket two-phase policy: D1 less-loaded while free slots
        # exist, D2 lower-min-score at full occupancy (ties -> primary)
        any_free = (occ1 < slots) | (occ2 < slots)
        d1_sel = (occ2 < occ1).astype(jnp.int32)
        d2_sel = ((m2h < m1h) | ((m2h == m1h) & (m2l < m1l))).astype(jnp.int32)
        tgtsel_ref[n] = jnp.where(any_free, d1_sel, d2_sel)

    br.query_loop(q_tile, 2, copies, body)


@functools.partial(jax.jit,
                   static_argnames=("q_tile", "use_digest", "interpret"))
def upsert_probe(tdigests, tkey_hi, tkey_lo, tscore_hi, tscore_lo,
                 bucket1, bucket2, qdigest, qkey_hi, qkey_lo, *,
                 q_tile: int, use_digest: bool, interpret: bool):
    """Fused per-query probe over both candidate bucket rows (N a multiple
    of q_tile; the wrapper pads).

    Returns (found, hit_sel, hit_slot, tgt_sel) int32 [N]:
      found    1 iff the key matched in either candidate bucket
      hit_sel  0 = matched (or defaulted) in bucket1, 1 = matched in bucket2
      hit_slot matching slot (0 on miss)
      tgt_sel  insertion target: 0 = bucket1, 1 = bucket2 (Alg. 3 selection)

    Single-bucket mode: pass bucket2 == bucket1; hit_sel/tgt_sel collapse
    to 0 by the tie--> -primary rule.
    """
    n = bucket1.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    nb, s = tdigests.shape
    group = br.digest_group(nb)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    out_spec, out_shape = br.smem_out(n)
    meta = lambda dt, rows=1: pltpu.VMEM((2, rows, s), dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n // q_tile,),
        in_specs=[br.hbm()] * _PLANES,
        out_specs=[out_spec] * 4,
        scratch_shapes=(
            ([meta(jnp.uint8, group)] + [meta(jnp.uint32)] * 4) * 2
            + [pltpu.SemaphoreType.DMA((2, 2 * _PLANES))]),
    )
    return pl.pallas_call(
        functools.partial(_probe_kernel, q_tile, group, use_digest),
        grid_spec=grid_spec,
        out_shape=[out_shape] * 4,
        interpret=interpret,
        name="hkv_upsert_probe",
    )(bucket1, bucket2, i32(qdigest), i32(qkey_hi), i32(qkey_lo),
      tdigests, tkey_hi, tkey_lo, tscore_hi, tscore_lo)


# =============================================================================
# claim_scan: rank-r victim extraction (empty-slot claim / argmin eviction)
# =============================================================================


def _claim_kernel(q_tile, bkt_ref, rank_ref, th, tl, tsh, tsl,
                  vslot_ref, vocc_ref, vsh_ref, vsl_ref, vkh_ref, vkl_ref,
                  hb, lb, shb, slb, sems):
    i = pl.program_id(0)
    slots = th.shape[1]
    planes = (th, tl, tsh, tsl)

    def copies(q, s):
        b = bkt_ref[i * q_tile + q]
        return [br.row_copy(p, buf.at[s], sems.at[s, j], b)
                for j, (p, buf) in enumerate(zip(planes, (hb, lb, shb, slb)))]

    def body(q, s):
        n = i * q_tile + q
        hh, ll, shi, slo = hb[s], lb[s], shb[s], slb[s]
        occ = (~empty_lanes(hh, ll)).astype(jnp.int32)
        lane = br.lane_iota(slots)

        # Pairwise lexicographic rank under the victim total order
        # (occupied asc, score_hi asc, score_lo asc, key_hi asc, key_lo
        # asc, slot asc).  rho[t] = #entries strictly weaker than slot t;
        # since the 6-tuples are distinct (slot tiebreak), rho is a
        # permutation and the rank-r victim is the unique slot with
        # rho == r.  Rows compare as `br.ordered` int32 [S, S] blocks:
        # row[t] along lanes against its transpose along sublanes.
        lt_m = jnp.zeros((slots, slots), jnp.bool_)
        eq_m = jnp.ones((slots, slots), jnp.bool_)
        for plane in (occ, br.ordered(shi), br.ordered(slo),
                      br.ordered(hh), br.ordered(ll), lane):
            across = jnp.broadcast_to(plane, (slots, slots))
            down = across.T
            lt_m = lt_m | (eq_m & (down < across))
            eq_m = eq_m & (down == across)
        rho = jnp.sum(lt_m.astype(jnp.int32), axis=0, keepdims=True)

        sel = rho == rank_ref[n]
        _, vslot = br.first_lane(sel)
        vslot_ref[n] = vslot
        vocc_ref[n] = jnp.sum(jnp.where(sel, occ, 0))
        vsh_ref[n] = br.pick(shi, sel)
        vsl_ref[n] = br.pick(slo, sel)
        vkh_ref[n] = br.pick(hh, sel)
        vkl_ref[n] = br.pick(ll, sel)

    br.query_loop(q_tile, 2, copies, body)


@functools.partial(jax.jit, static_argnames=("q_tile", "interpret"))
def claim_scan(tkey_hi, tkey_lo, tscore_hi, tscore_lo, buckets, rank, *,
               q_tile: int, interpret: bool):
    """Per-query rank-r victim of each target bucket row (N a multiple of
    q_tile; the wrapper pads).

    buckets : int32 [N] target bucket per (canonically sorted) miss
    rank    : int32 [N] within-bucket canonical rank, pre-clipped to [0, S)

    Returns (slot, occupied, score_hi, score_lo, key_hi, key_lo), each [N]:
    the entry the rank-r incoming key is paired against — an empty slot
    (claim), or the rank-r weakest live entry (evict if strictly beaten,
    reject otherwise).  Reads only: claims are scattered by the caller, so
    queries stay independent and the pass pipelines like the find path.
    """
    n = buckets.shape[0]
    assert n % q_tile == 0, "wrapper must pad to a q_tile multiple"
    s = tkey_hi.shape[1]
    out_spec, out_shape = br.smem_out(n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // q_tile,),
        in_specs=[br.hbm()] * 4,
        out_specs=[out_spec] * 6,
        scratch_shapes=([pltpu.VMEM((2, 1, s), jnp.uint32)] * 4
                        + [pltpu.SemaphoreType.DMA((2, 4))]),
    )
    vslot, vocc, vsh, vsl, vkh, vkl = pl.pallas_call(
        functools.partial(_claim_kernel, q_tile),
        grid_spec=grid_spec,
        out_shape=[out_shape] * 6,
        interpret=interpret,
        name="hkv_claim_scan",
    )(buckets, rank, tkey_hi, tkey_lo, tscore_hi, tscore_lo)
    u32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    return vslot, vocc, u32(vsh), u32(vsl), u32(vkh), u32(vkl)
