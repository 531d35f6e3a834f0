"""Batch-synchronous bucket merge — the TPU closure of paper Algorithms 2 & 3.

The paper resolves each upsert with a per-warp CAS loop.  XLA/TPU has no
device-wide CAS; it has world-class sorts and segmented reductions.  This
module re-derives the paper's in-line score-driven upsert as a deterministic
*batch closure*:

  Applying Algorithm 2 sequentially, in canonical order (hits first, then
  misses per bucket in descending incoming-score order), to every entry of a
  batch yields per bucket exactly the **top-S-by-score union** of
  (existing entries ∪ incoming entries), with ties won by existing entries
  (then by lower key).  We compute that closure directly:

  phase 1 (non-structural) — batch keys already present are *updates*:
      value/score scatter at their (bucket, slot); no structure changes.
  phase 2 (structural)     — remaining keys are *insertions*: per target
      bucket, pair the r-th best incoming entry with the r-th weakest
      existing slot (empties weakest, then ascending score).  The classic
      two-sorted-lists argument shows this pairing realizes the top-S
      union merge:  incoming rank r is admitted iff it strictly beats
      victim rank r; admissions are a prefix of incoming ranks.

Properties preserved from the paper:
  * CS1 — every full-bucket upsert resolves in place (evict or reject);
  * CS2 — no rehash, no capacity failure, table shape never changes;
  * admission control (Alg. 2 line 12 / Alg. 3 line 7): an incoming entry
    that cannot beat the weakest survivor is Rejected;
  * eviction always removes the bucket-minimum-score entry(s);
  * dual-bucket two-phase policy (Alg. 3): D1 less-loaded while free slots
    exist, D2 lower-min-score at full occupancy.

Deviation (documented): on *exact* score ties Alg. 2 admits the incoming
key (`s < s_min` rejects), which makes sequential outcomes depend on batch
order.  The batch closure breaks ties in favor of existing entries, making
the result order-independent and idempotent.  LRU/epoch clocks are strictly
monotonic so ties between old and new scores only arise for LFU count
collisions and custom scores; Exp#3d shows admission behaviour matches the
paper's Table 9 in both regimes.

Everything is static-shape: a batch of N keys costs O(N log N) sort work
plus O(N·S) gathered bucket rows — no data-dependent shapes, no host
round-trips, jit/shard_map friendly.

The heavy stages (locate, target selection, victim extraction, value
gather/scatter) are pluggable via `UpsertStages` (DESIGN.md §4): the
pure-jnp defaults below are the reference, `repro.kernels.ops` swaps in
the fused Pallas kernels.  The orchestration — and therefore every
ordering decision — is shared, so the backends are bit-identical.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import find as find_mod
from repro.core import roles
from repro.core import table as table_mod
from repro.core import u64
from repro.core.table import HKVConfig, HKVState
from repro.core.u64 import U64

# Per-entry status codes (reported in the original batch order).
STATUS_INVALID = np.int8(0)   # input slot held the EMPTY sentinel
STATUS_UPDATED = np.int8(1)   # key existed: value/score updated in place
STATUS_INSERTED = np.int8(2)  # inserted into an empty slot
STATUS_EVICTED = np.int8(3)   # inserted by evicting a minimum-score entry
STATUS_REJECTED = np.int8(4)  # admission control refused the entry


class UpsertStages(NamedTuple):
    """Pluggable backends for the heavy stages of the batch closure.

    `upsert` is one deterministic orchestration with five replaceable
    stages.  The defaults (`jnp_stages`) are the pure-jnp reference; the
    Pallas path (`repro.kernels.ops.upsert_kernel`) swaps in kernel-backed
    implementations of the same contracts.  Because the orchestration —
    dedupe, canonical sort, rank pairing, admission control, status
    accounting — is shared, the two backends are bit-identical by
    construction wherever the stage contracts are met (DESIGN.md §4).

      locate(state, cfg, keys, probe) -> find_mod.Locate
          digest pre-filter + full-key match of the deduped batch.
      select_target(state, cfg, probe) -> int32 [N]
          dual-bucket two-phase target selection (post-phase-1 state).
      victim_at_rank(state, cfg, bkt_g, rank) ->
          (slot int32[N], occupied bool[N], score U64[N], key U64[N])
          the rank-th weakest entry of each row of `bkt_g` under the
          total victim order (occupied asc, score asc, key asc, slot asc):
          empties first (lowest slot first), then ascending score.
      gather_values(cfg, values, rows, mask) -> [N, Dtot]
          masked row gather (evicted-value hand-off); zeros where ~mask.
      scatter_values(cfg, values, rows, updates, mask) -> new values
          masked row scatter; masked rows must be unique within the batch.
    """

    locate: object
    select_target: object
    victim_at_rank: object
    gather_values: object
    scatter_values: object


class EvictionStream(NamedTuple):
    """Displaced `(key, value, score)` pairs of one structural op — the
    paper's in-launch eviction hand-off (§3.6) as a first-class typed
    result.  This is the transport contract the tier hierarchy rides on
    (`core/tiered.py`): a hot-tier upsert's stream upserts into the cold
    tier (demotion), a promotion's displaced victims cascade back down.

    All arrays share the batch length N and align POSITIONALLY with the
    op's input batch: lane i carries the pair displaced by input key i
    (mask False = lane displaced nothing; its key/value/score lanes are
    zeros, NOT the EMPTY sentinel — mask before reusing them as keys,
    e.g. via `masked_keys()`)."""

    key_hi: jax.Array    # uint32 [N]
    key_lo: jax.Array    # uint32 [N]
    values: jax.Array    # vdtype [N, Dtot] full-width rows (incl. aux cols)
    score_hi: jax.Array  # uint32 [N]
    score_lo: jax.Array  # uint32 [N]
    mask: jax.Array      # bool [N] — lane carries a displaced pair

    @property
    def keys(self) -> U64:
        return U64(self.key_hi, self.key_lo)

    @property
    def scores(self) -> U64:
        return U64(self.score_hi, self.score_lo)

    def masked_keys(self) -> U64:
        """Keys with non-displacing lanes set to the EMPTY sentinel — the
        form a downstream table op ingests directly (EMPTY lanes are
        ignored by every op; raw zero lanes would be a VALID key 0)."""
        return U64(
            jnp.where(self.mask, self.key_hi, jnp.uint32(u64.EMPTY_HI)),
            jnp.where(self.mask, self.key_lo, jnp.uint32(u64.EMPTY_LO)),
        )

    def count(self) -> jax.Array:
        return jnp.sum(self.mask.astype(jnp.int32))

    @classmethod
    def zero(cls, n: int, vdim: int, vdtype) -> "EvictionStream":
        """A stream of n lanes displacing nothing (n=0: the placeholder
        returned when the caller did not request the eviction hand-off)."""
        z = jnp.zeros((n,), jnp.uint32)
        return cls(
            key_hi=z, key_lo=z,
            values=jnp.zeros((n, vdim), vdtype),
            score_hi=z, score_lo=z,
            mask=jnp.zeros((n,), bool),
        )


class MergeResult(NamedTuple):
    state: HKVState
    status: jax.Array            # int8 [N] in original batch order
    # The eviction hand-off: lanes populated iff return_evicted (else the
    # zero-length EvictionStream placeholder).
    evicted: EvictionStream
    # Post-op key locations (batch order), produced as a byproduct of the
    # closure so callers like find_or_insert need NO extra probe passes:
    found: jax.Array             # bool [N] — key existed BEFORE this op
    loc: find_mod.Locate         # where each key lives AFTER this op
                                 # (loc.found = present now: hit or admitted)


@roles.scoped("hkv.closure")
def _dedupe_sort(keys: U64):
    """Sort batch by key; derive group ids / multiplicities / last-writer index.

    Returns (in key-sorted space): keys_s, idx_s (original positions),
    gid (group id), count (group multiplicity broadcast to members),
    last_idx (original index of the group's last occurrence — the batch's
    last writer), rep_mask (True at each group's first sorted element for
    valid keys).
    """
    n = keys.hi.shape[0]
    idx_s = u64.lexsort((keys.hi, keys.lo))
    keys_s = U64(keys.hi[idx_s], keys.lo[idx_s])
    prev = U64(jnp.roll(keys_s.hi, 1), jnp.roll(keys_s.lo, 1))
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), u64.ne(keys_s, prev).astype(bool)[1:]]
    )
    gid = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    ones = jnp.ones((n,), jnp.uint32)
    counts = jax.ops.segment_sum(ones, gid, num_segments=n)
    last_idx = jax.ops.segment_max(idx_s, gid, num_segments=n)
    valid_s = ~u64.is_empty(keys_s)
    rep_mask = is_first & valid_s
    return keys_s, idx_s, gid, counts[gid], last_idx[gid], rep_mask


class DedupeResult(NamedTuple):
    """Key-batch dedupe in sorted space (the engine's canonical form).

    All arrays have the batch length N.  `unique` carries each group's
    representative key at the group's first sorted slot and the EMPTY
    sentinel elsewhere — exactly the shape table ops expect (duplicates
    masked out, constant shape preserved).
    """

    unique: U64            # [N] EMPTY-padded representative keys (sorted space)
    idx_sorted: jax.Array  # int32 [N] original position of sorted slot j
    gid: jax.Array         # int32 [N] group id of sorted slot j
    rep_mask: jax.Array    # bool [N] True at each group's first sorted slot
    last_index: jax.Array  # int32 [N] original index of the group's LAST occurrence
    inverse: jax.Array     # int32 [N] original position -> its rep's sorted slot


@roles.scoped("hkv.closure")
def dedupe_keys(keys: U64) -> DedupeResult:
    """Public dedupe over the canonical key sort (shared by the engine and
    the api layer — see `repro.core.api.dedupe_keys` for the normalizing
    wrapper consumers use): route/reduce per `unique`, then map per-group
    results back with `inverse`."""
    n = keys.hi.shape[0]
    keys_s, idx_s, gid, _count, last_idx, rep = _dedupe_sort(keys)
    unique = u64.select(rep, keys_s, u64.empty_sentinel((n,)))
    rep_pos = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32), gid, num_segments=n
    )
    inverse = jnp.zeros((n,), jnp.int32).at[idx_s].set(rep_pos[gid])
    return DedupeResult(unique=unique, idx_sorted=idx_s, gid=gid,
                        rep_mask=rep, last_index=last_idx, inverse=inverse)


def _bucket_minscore_and_occ(state: HKVState, bucket: jax.Array):
    """(occupancy[N], min-score[N] as U64) of the given bucket rows.

    Empty slots are excluded from the min (treated as +inf); a fully empty
    bucket reports the all-ones max sentinel.
    """
    occ_row = ~u64.is_empty(U64(state.key_hi[bucket], state.key_lo[bucket]))
    occ = jnp.sum(occ_row.astype(jnp.int32), axis=1)
    shi = jnp.where(occ_row, state.score_hi[bucket], jnp.uint32(0xFFFFFFFF))
    slo = jnp.where(occ_row, state.score_lo[bucket], jnp.uint32(0xFFFFFFFF))
    # lexicographic min via single sort-free reduction: min hi, then min lo | hi==minhi
    min_hi = jnp.min(shi, axis=1)
    lo_cand = jnp.where(shi == min_hi[:, None], slo, jnp.uint32(0xFFFFFFFF))
    min_lo = jnp.min(lo_cand, axis=1)
    return occ, U64(min_hi, min_lo)


@roles.scoped("hkv.closure")
def _select_target_bucket(
    state: HKVState, cfg: HKVConfig, probe: find_mod.Probe
) -> jax.Array:
    """Dual-bucket two-phase selection (paper Alg. 3 / Fig. 5).

    D1 (warm-up): while either candidate has a free slot, insert into the
    less-occupied bucket (ties -> primary).  D2 (steady state): both full,
    evict in the bucket with the lower minimum score (ties -> primary).
    """
    if cfg.buckets_per_key == 1:
        return probe.bucket1
    s = cfg.slots_per_bucket
    occ1, min1 = _bucket_minscore_and_occ(state, probe.bucket1)
    occ2, min2 = _bucket_minscore_and_occ(state, probe.bucket2)
    any_free = (occ1 < s) | (occ2 < s)
    d1 = jnp.where(occ2 < occ1, probe.bucket2, probe.bucket1)
    d2 = jnp.where(u64.lt(min2, min1), probe.bucket2, probe.bucket1)
    return jnp.where(any_free, d1, d2)


@roles.scoped("hkv.closure")
def _jnp_victim_at_rank(state: HKVState, cfg: HKVConfig, bkt_g: jax.Array,
                        rank: jax.Array):
    """Rank-th weakest entry per gathered bucket row, via a per-row sort.

    Victim order is the 6-key lexicographic sort (occupied asc, score asc,
    key asc, slot asc) — fully deterministic: empties claim ascending slot
    order, occupied entries ascend by score then key (unique table-wide).
    """
    n = bkt_g.shape[0]
    s = cfg.slots_per_bucket
    row_occ = ~u64.is_empty(U64(state.key_hi[bkt_g], state.key_lo[bkt_g]))
    occ, shi, slo, khi, klo = (row_occ.astype(jnp.uint32),
                               state.score_hi[bkt_g], state.score_lo[bkt_g],
                               state.key_hi[bkt_g], state.key_lo[bkt_g])
    # slot order breaks the remaining ties: lexsort keeps index order
    order = u64.lexsort((occ, shi, slo, khi, klo), axis=1)
    r_cl = jnp.clip(rank, 0, s - 1)[:, None]
    v_slot = jnp.take_along_axis(order, r_cl, axis=1)
    take = lambda a: jnp.take_along_axis(a, v_slot, axis=1)[:, 0]
    return (
        v_slot[:, 0],
        take(occ).astype(bool),
        U64(take(shi), take(slo)),
        U64(take(khi), take(klo)),
    )


def _jnp_gather_values(cfg: HKVConfig, values: jax.Array, rows: jax.Array,
                       mask: jax.Array) -> jax.Array:
    out = table_mod.tier_gather(cfg.value_tier, values, jnp.where(mask, rows, 0),
                                cfg.total_value_dim)
    return jnp.where(mask[:, None], out, jnp.zeros_like(out))


def _jnp_scatter_values(cfg: HKVConfig, values: jax.Array, rows: jax.Array,
                        updates: jax.Array, mask: jax.Array) -> jax.Array:
    oob = cfg.capacity  # past the last slot: dropped (masked-out lanes)
    return table_mod.tier_scatter(
        cfg.value_tier, values, jnp.where(mask, rows, oob), updates
    )


def jnp_stages() -> UpsertStages:
    """The pure-jnp reference implementation of every upsert stage."""
    return UpsertStages(
        locate=lambda state, cfg, keys, probe: find_mod.locate(state, cfg, keys, probe),
        select_target=_select_target_bucket,
        victim_at_rank=_jnp_victim_at_rank,
        gather_values=_jnp_gather_values,
        scatter_values=_jnp_scatter_values,
    )


@roles.scoped("hkv.closure")
def upsert(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    *,
    custom_scores: Optional[U64] = None,
    write_hit_values: bool = True,
    update_hit_scores: bool = True,
    insert_values: Optional[jax.Array] = None,
    return_evicted: bool = False,
    stages: Optional[UpsertStages] = None,
    loc: Optional[find_mod.Locate] = None,
) -> MergeResult:
    """The batch closure of insert_or_assign / find_or_insert / insert_and_evict.

    values        : [N, Dtot] rows written on hit (when write_hit_values)
                    and inserted on miss (unless insert_values overrides).
    insert_values : optional distinct rows for the insertion path
                    (find_or_insert: hits keep their value, misses get inits).
    loc           : optional precomputed locate of `keys` (BATCH order)
                    against this state's key planes — the PR-2 probe-sharing
                    seam: when a caller just probed the same batch (e.g. the
                    tier hierarchy's pre-pass), the closure permutes it into
                    its sorted space instead of issuing its own locate.
                    Locate output depends only on the key plane, so the
                    substitution is exact.
    """
    n = keys.hi.shape[0]
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    vdim = cfg.total_value_dim
    policy = cfg.policy
    if insert_values is None:
        insert_values = values
    if stages is None:
        stages = jnp_stages()

    # One clock tick per batched op (the paper's per-launch device clock).
    state = table_mod.advance_clock(state)
    clock, epoch = state.clock, state.epoch

    # ---- dedupe ------------------------------------------------------------
    keys_s, idx_s, gid, count_s, last_idx_s, rep_mask = _dedupe_sort(keys)
    custom_s = None
    if custom_scores is not None:
        custom_s = U64(custom_scores.hi[last_idx_s], custom_scores.lo[last_idx_s])

    # status accumulated per group id, mapped back to batch order at the end
    status_g = jnp.zeros((n,), jnp.int8)

    # ---- phase 1: hits (non-structural updater work) ------------------------
    probe_s = find_mod.probe_keys(cfg, keys_s)
    if loc is None:
        loc = stages.locate(state, cfg, keys_s, probe_s)
    else:
        # caller-provided batch-order locate -> sorted space.  EMPTY lanes
        # are force-missed (a caller may pass a probe of the unmasked batch;
        # every use of `loc` below is already rep_mask/valid-gated, but the
        # mask keeps the permuted Locate self-consistent).
        valid_s = ~u64.is_empty(keys_s)
        loc = find_mod.Locate(
            found=loc.found[idx_s] & valid_s,
            bucket=loc.bucket[idx_s],
            slot=loc.slot[idx_s],
            row=loc.row[idx_s],
        )
    hit = loc.found & rep_mask

    old_sc = U64(state.score_hi[loc.bucket, loc.slot], state.score_lo[loc.bucket, loc.slot])
    new_sc = policy.update_score(old_sc, clock, epoch, count_s, custom_s)
    hb = jnp.where(hit & jnp.asarray(update_hit_scores), loc.bucket, b)  # OOB -> drop
    state = state._replace(
        score_hi=state.score_hi.at[hb, loc.slot].set(new_sc.hi, mode="drop"),
        score_lo=state.score_lo.at[hb, loc.slot].set(new_sc.lo, mode="drop"),
    )
    if write_hit_values:
        state = state._replace(
            values=stages.scatter_values(
                cfg, state.values, loc.row,
                values[last_idx_s].astype(state.values.dtype), hit,
            )
        )
    status_g = status_g.at[gid].max(jnp.where(hit, STATUS_UPDATED, STATUS_INVALID))

    # ---- phase 2: misses (structural inserter work) --------------------------
    miss = rep_mask & ~loc.found
    target = stages.select_target(state, cfg, probe_s)
    init_sc = policy.init_score(clock, epoch, count_s, custom_s, (n,))

    # bucket-sort the misses: (bucket, score desc, key asc) — canonical order
    bkt_key = jnp.where(miss, target, b).astype(jnp.int32)
    # (bitwise-not => descending score order)
    order = u64.lexsort((bkt_key, ~init_sc.hi, ~init_sc.lo, keys_s.hi,
                         keys_s.lo))
    (bkt_m, khi_m, klo_m, idx_m, vrow_m, dig_m, shi_m, slo_m, gid_m) = (
        a[order] for a in (bkt_key, keys_s.hi, keys_s.lo, idx_s, last_idx_s,
                           probe_s.digest, init_sc.hi, init_sc.lo, gid))
    mask_m = bkt_m < b
    iota = jnp.arange(n, dtype=jnp.int32)
    is_newb = jnp.concatenate([jnp.ones((1,), bool), (bkt_m[1:] != bkt_m[:-1])])
    run_start = jax.lax.cummax(jnp.where(is_newb, iota, -1))
    rank = iota - run_start  # within-bucket rank r (incoming, descending score)

    # victim order per touched bucket row: empties first (ascending slot),
    # then ascending score, ties broken by ascending key then slot — a total
    # order, so victim choice is deterministic and backend-independent
    bkt_g = jnp.clip(bkt_m, 0, b - 1)
    victim_slot, victim_occ, victim_sc, victim_key = stages.victim_at_rank(
        state, cfg, bkt_g, rank
    )
    inc_sc = U64(shi_m, slo_m)
    # admission control: strictly beat the paired victim (existing wins ties)
    admitted = mask_m & (rank < s) & (~victim_occ | u64.gt(inc_sc, victim_sc))
    evicts = admitted & victim_occ

    # evicted outputs must be gathered before the overwrite
    victim_row = bkt_g * s + victim_slot
    if return_evicted:
        ev_values = stages.gather_values(cfg, state.values, victim_row, evicts)
    else:
        ev_values = jnp.zeros((n, vdim), state.values.dtype)

    # structural scatter (conflict-free: distinct (bucket, victim_slot) pairs)
    tb = jnp.where(admitted, bkt_m, b)
    state = state._replace(
        key_hi=state.key_hi.at[tb, victim_slot].set(khi_m, mode="drop"),
        key_lo=state.key_lo.at[tb, victim_slot].set(klo_m, mode="drop"),
        digests=state.digests.at[tb, victim_slot].set(dig_m, mode="drop"),
        score_hi=state.score_hi.at[tb, victim_slot].set(shi_m, mode="drop"),
        score_lo=state.score_lo.at[tb, victim_slot].set(slo_m, mode="drop"),
        values=stages.scatter_values(
            cfg, state.values, victim_row,
            insert_values[vrow_m].astype(state.values.dtype), admitted,
        ),
    )
    status_m = jnp.where(
        admitted,
        jnp.where(evicts, STATUS_EVICTED, STATUS_INSERTED),
        jnp.where(mask_m, STATUS_REJECTED, STATUS_INVALID),
    ).astype(jnp.int8)
    status_g = status_g.at[gid_m].max(status_m)

    # map group status back to original batch order (duplicates share status)
    status = jnp.zeros((n,), jnp.int8).at[idx_s].set(status_g[gid])

    # ---- post-op locations (batch order) ------------------------------------
    # The closure already knows where every key ended up: hits stayed at
    # their located (bucket, slot); admitted misses took their paired
    # victim's slot in the target bucket.  Publishing this kills the
    # pre/post re-probe passes in find_or_insert (one probe total).
    # A group is either a hit or a miss, so the two scatters are disjoint.
    #
    # One subtlety: a HIT can lose its slot within the same batch — an
    # admitted miss whose init score beats the hit's just-updated score
    # (reachable under LFU-family/custom policies, never under monotone
    # LRU clocks) claims it as a victim.  The published location must
    # then report the key as GONE, exactly like the old post-insert
    # re-probe did: check the final key plane at the hit's position.
    pos_b = jnp.zeros((n,), jnp.int32)
    pos_s = jnp.zeros((n,), jnp.int32)
    pos_in = jnp.zeros((n,), bool)
    hit_live = hit & find_mod.match_lanes(
        state.key_hi[loc.bucket, loc.slot], state.key_lo[loc.bucket, loc.slot],
        keys_s.hi, keys_s.lo)
    hg = jnp.where(hit_live, gid, n)
    pos_b = pos_b.at[hg].set(loc.bucket, mode="drop")
    pos_s = pos_s.at[hg].set(loc.slot, mode="drop")
    pos_in = pos_in.at[hg].set(True, mode="drop")
    ag = jnp.where(admitted, gid_m, n)
    pos_b = pos_b.at[ag].set(bkt_m, mode="drop")
    pos_s = pos_s.at[ag].set(victim_slot, mode="drop")
    pos_in = pos_in.at[ag].set(True, mode="drop")
    # sorted-space per-group results -> original batch order (dups share)
    to_batch = lambda a: jnp.zeros((n,), a.dtype).at[idx_s].set(a[gid])
    post_loc = find_mod.Locate(
        found=to_batch(pos_in),
        bucket=to_batch(pos_b),
        slot=to_batch(pos_s),
        row=to_batch(pos_b * s + pos_s),
    )
    pre_found = jnp.zeros((n,), bool).at[idx_s].set(loc.found)

    if return_evicted:
        zero32 = jnp.zeros((n,), jnp.uint32)
        oe = jnp.where(evicts, idx_m, n)  # original position of the evictor
        stream = EvictionStream(
            key_hi=zero32.at[oe].set(victim_key.hi, mode="drop"),
            key_lo=zero32.at[oe].set(victim_key.lo, mode="drop"),
            values=jnp.zeros((n, vdim), state.values.dtype)
            .at[oe]
            .set(ev_values, mode="drop"),
            score_hi=zero32.at[oe].set(victim_sc.hi, mode="drop"),
            score_lo=zero32.at[oe].set(victim_sc.lo, mode="drop"),
            mask=jnp.zeros((n,), bool).at[oe].set(evicts, mode="drop"),
        )
    else:
        stream = EvictionStream.zero(0, vdim, state.values.dtype)
    return MergeResult(state=state, status=status, evicted=stream,
                       found=pre_found, loc=post_loc)
