"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose/equal).
These are also the implementations XLA runs where a kernel is not
profitable (tiny batches) — the wrapper in ops.py dispatches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import find, u64
from repro.core.table import tier_gather, tier_scatter


def digest_scan_ref(
    tdigests: jax.Array,   # uint8  [B, S] table digest rows
    tkey_hi: jax.Array,    # uint32 [B, S]
    tkey_lo: jax.Array,    # uint32 [B, S]
    buckets: jax.Array,    # int32  [N] bucket per query
    qdigest: jax.Array,    # uint32 [N] query digest (widened for SMEM)
    qkey_hi: jax.Array,    # uint32 [N]
    qkey_lo: jax.Array,    # uint32 [N]
) -> tuple[jax.Array, jax.Array]:
    """(slot int32 [N], found int32 [N]) — Algorithm 1 over one bucket row.

    Digest pre-filter then full key compare; the first matching slot wins
    (at most one can match by the table's key-uniqueness invariant).
    """
    m = find.match_lanes(tkey_hi[buckets], tkey_lo[buckets],
                         qkey_hi[:, None], qkey_lo[:, None],
                         tdigests[buckets].astype(jnp.uint32),
                         qdigest[:, None])
    found = jnp.any(m, axis=1).astype(jnp.int32)
    slot = jnp.argmax(m, axis=1).astype(jnp.int32)
    return slot, found


def find_scan_ref(
    tdigests: jax.Array,   # uint8  [B, S]
    tkey_hi: jax.Array,    # uint32 [B, S]
    tkey_lo: jax.Array,    # uint32 [B, S]
    tscore_hi: jax.Array,  # uint32 [B, S]
    tscore_lo: jax.Array,  # uint32 [B, S]
    tvalues: jax.Array,    # packed value plane (position addressing §3.6)
    bucket1: jax.Array,    # int32  [N] primary candidate bucket
    bucket2: jax.Array,    # int32  [N] secondary candidate (== bucket1 single)
    qdigest: jax.Array,    # uint32 [N]
    qkey_hi: jax.Array,    # uint32 [N]
    qkey_lo: jax.Array,    # uint32 [N]
    width: int,            # logical value row width
    use_digest: bool = True,
):
    """Ground truth for the fused find kernel (find_scan.py).

    Per query, over both candidate bucket rows: digest pre-filter + full-key
    confirm (the `core.find._match_in_bucket` formula), dual-bucket merge
    (hit1 wins; miss defaults to bucket1/slot0), score readout at the hit
    slot, and the hit row's value slice (zeros on miss).

    Returns (found i32 [N], sel i32 [N] — 0=bucket1/1=bucket2, slot i32 [N],
    score_hi u32 [N], score_lo u32 [N], values [N, V]).
    """
    s = tdigests.shape[1]

    def match(buckets):
        if use_digest:
            m = find.match_lanes(tkey_hi[buckets], tkey_lo[buckets],
                                 qkey_hi[:, None], qkey_lo[:, None],
                                 tdigests[buckets].astype(jnp.uint32),
                                 qdigest[:, None])
        else:
            m = find.match_lanes(tkey_hi[buckets], tkey_lo[buckets],
                                 qkey_hi[:, None], qkey_lo[:, None])
        return jnp.any(m, axis=1), jnp.argmax(m, axis=1).astype(jnp.int32)

    hit1, slot1 = match(bucket1)
    hit2, slot2 = match(bucket2)
    found = hit1 | hit2
    sel = jnp.where(hit1, 0, jnp.where(hit2, 1, 0)).astype(jnp.int32)
    slot = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
    bucket = jnp.where(sel == 1, bucket2, bucket1)
    shi = jnp.where(found, tscore_hi[bucket, slot], 0)
    slo = jnp.where(found, tscore_lo[bucket, slot], 0)
    vals = tier_gather("hbm", tvalues, bucket * s + slot, width)
    vals = jnp.where(found[:, None], vals, jnp.zeros_like(vals))
    return found.astype(jnp.int32), sel, slot, shi, slo, vals


def update_scan_ref(
    tdigests: jax.Array,   # uint8  [B, S]
    tkey_hi: jax.Array,    # uint32 [B, S]
    tkey_lo: jax.Array,    # uint32 [B, S]
    tvalues: jax.Array,    # packed value plane (position addressing §3.6)
    bucket1: jax.Array,    # int32  [N] primary candidate bucket
    bucket2: jax.Array,    # int32  [N] secondary candidate (== bucket1 single)
    qdigest: jax.Array,    # uint32 [N]
    qkey_hi: jax.Array,    # uint32 [N]
    qkey_lo: jax.Array,    # uint32 [N]
    qvalid: jax.Array,     # int32  [N] — 0 gates the write (EMPTY padding)
    grads: jax.Array,      # [N, dim] segment-summed gradient rows
    opt,                   # SparseOptimizer (static variant)
    dim: int,
    width: int,            # logical value row width (dim + aux)
    use_digest: bool = True,
):
    """Ground truth for the fused updater kernel (update_scan.py).

    Per query, over both candidate bucket rows: digest pre-filter + full-key
    confirm (the shared `core.find.match_lanes` formula), dual-bucket merge
    (hit1 wins), then a masked row read-modify-write: the hit row becomes
    ``opt.apply(row, grads[i], dim)``; miss or qvalid==0 lanes leave the
    plane untouched (cache semantics — un-admitted keys never train).

    The qvalid gate exists because an EMPTY-padded query key *matches*
    empty slots (both are the all-ones sentinel in the key planes): a
    read-only kernel can re-mask afterwards, a writing kernel cannot.

    Returns (found i32 [N], new_values — the updated plane).
    """
    s = tdigests.shape[1]

    def match(buckets):
        if use_digest:
            m = find.match_lanes(tkey_hi[buckets], tkey_lo[buckets],
                                 qkey_hi[:, None], qkey_lo[:, None],
                                 tdigests[buckets].astype(jnp.uint32),
                                 qdigest[:, None])
        else:
            m = find.match_lanes(tkey_hi[buckets], tkey_lo[buckets],
                                 qkey_hi[:, None], qkey_lo[:, None])
        return jnp.any(m, axis=1), jnp.argmax(m, axis=1).astype(jnp.int32)

    hit1, slot1 = match(bucket1)
    hit2, slot2 = match(bucket2)
    found = (hit1 | hit2) & (qvalid != 0)
    sel = jnp.where(hit1, 0, jnp.where(hit2, 1, 0)).astype(jnp.int32)
    slot = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
    bucket = jnp.where(sel == 1, bucket2, bucket1)
    row = bucket * s + slot
    raw = tier_gather("hbm", tvalues, row, width)
    new_rows = opt.apply(raw, grads, dim).astype(tvalues.dtype)
    r = jnp.where(found, row, tkey_hi.size)  # past the last slot: dropped
    new_values = tier_scatter("hbm", tvalues, r,
                              jnp.where(found[:, None], new_rows, raw))
    return found.astype(jnp.int32), new_values


def gather_rows_ref(
    values: jax.Array,  # packed value plane
    rows: jax.Array,    # int32 [N] logical rows (in range)
    mask: jax.Array,    # int32/bool [N] — rows with mask==0 return zeros
    width: int,         # logical row width
) -> jax.Array:
    """Position-addressed value gather (§3.6): out[i] = row rows[i]."""
    out = tier_gather("hbm", values, rows, width)
    return jnp.where(mask.astype(bool)[:, None], out, jnp.zeros_like(out))


def scatter_rows_ref(
    values: jax.Array,  # packed value plane
    rows: jax.Array,    # int32 [N] — must be unique where mask set
    updates: jax.Array,  # [N, W]
    mask: jax.Array,    # [N]
    add: bool,
    num_rows: int,      # logical rows in the plane (the table's capacity)
) -> jax.Array:
    """Updater-role write-back: row rows[i] (+)= updates[i] where mask."""
    r = jnp.where(mask.astype(bool), rows, num_rows)  # past the end: dropped
    return tier_scatter("hbm", values, r, updates, add=add)


def bucket_stats_ref(
    tkey_hi: jax.Array,   # uint32 [B, S]
    tkey_lo: jax.Array,   # uint32 [B, S]
    score_hi: jax.Array,  # uint32 [B, S]
    score_lo: jax.Array,  # uint32 [B, S]
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-bucket (occupancy, min-score hi, min-score lo, argmin slot).

    Empty slots (all-ones key sentinel) are excluded from the min; a fully
    empty bucket reports the all-ones max score and argmin slot 0.
    """
    occ_mask = ~u64.empty_lanes(tkey_hi, tkey_lo)
    occ = jnp.sum(occ_mask.astype(jnp.int32), axis=1)
    ones = jnp.uint32(0xFFFFFFFF)
    shi = jnp.where(occ_mask, score_hi, ones)
    slo = jnp.where(occ_mask, score_lo, ones)
    min_hi = jnp.min(shi, axis=1)
    lo_cand = jnp.where(shi == min_hi[:, None], slo, ones)
    min_lo = jnp.min(lo_cand, axis=1)
    is_min = find.match_lanes(shi, slo, min_hi[:, None], min_lo[:, None])
    argmin = jnp.argmax(is_min, axis=1).astype(jnp.int32)
    return occ, min_hi, min_lo, argmin
