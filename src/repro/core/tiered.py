"""TieredHKVTable — §3.6 tiered key-value separation grown into a real
two-tier cache hierarchy (DESIGN.md §2.5).

The paper's headline contract — every full-bucket upsert resolves by
eviction, with displaced pairs handed off in the same launch — is exactly
the transport a storage hierarchy needs.  This module composes two full
HKV tables behind the `KVTable` protocol:

  hot tier   a small, fast table whose value plane stays in HBM;
  cold tier  a larger table whose value plane uses the existing 'hmem'
             placement (`HKVConfig.value_tier`), HugeCTR/HPS-style.

Two data motions, both riding the typed `EvictionStream`
(`core.merge.EvictionStream`):

  DEMOTION    every hot-tier structural op runs as `insert_and_evict`;
              its displaced `(key, value, score)` pairs — plus incoming
              pairs the hot tier REJECTED — upsert into the cold tier
              with scores translated across the per-tier policies
              (`translate_scores`).  Nothing leaves the hierarchy except
              at the cold tier's own admission/eviction boundary, and
              those losses are counted and reported (`.dropped`).
  PROMOTION   hot-tier find misses probe the cold tier; cold hits are
              re-admitted into the hot tier (full-width rows, so aux
              optimizer columns travel with the embedding), and the hot
              entries THEY displace cascade back down through the same
              demotion path.  The hot tier is therefore an
              inclusive-on-access cache: a promoted key keeps its cold
              copy, which is freshened by write-back whenever the hot
              copy is demoted; reads always prefer the hot copy, so the
              cold copy is only visible after such a write-back.

Capacity semantics downstream: every consumer that drives a `KVTable`
handle upgrades from "table must fit in HBM" to "hot set must fit in
HBM" — the cold tier absorbs the working set's tail.

Layering: this module lives in `repro.core` and may call the op engine
(`core.ops`) directly; external consumers use the handle, which is a
registered pytree (the two tier handles are its children, so jit /
donate / scan / checkpoint-tree behavior is inherited from `HKVTable`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import find as find_mod
from repro.core import ops as ops_mod
from repro.core import u64
from repro.core.api import HKVTable, normalize_keys, _opt_keys
from repro.core.merge import EvictionStream
from repro.core.scores import ScorePolicy
from repro.core.table import HKVConfig, HKVState
from repro.core.u64 import U64


# =============================================================================
# Score translation across per-tier policies
# =============================================================================


def translate_scores(src: ScorePolicy, dst: ScorePolicy,
                     scores: U64) -> Optional[U64]:
    """Map scores from the source tier's policy domain into admission
    scores for the destination tier (DESIGN.md §2.5).

    * dst 'custom'  — pass the source scores through verbatim.  Every
      policy's scores are uint64 with eviction order = ascending value,
      so the u64 total order carries the source tier's relative
      hot/coldness into the destination unchanged.  This is the default
      cold-tier policy (`TieredHKVTable.create`): demoted pairs compete
      in the cold tier by exactly the score that got them evicted.
    * any other dst — return None: the destination stamps its own
      (clock/epoch/count) score at admission time.  Recency restarts and
      LFU-family counters restart at the batch multiplicity — per-tier
      clock domains are independent, so importing a foreign clock value
      would corrupt the destination's order.  Callers needing full
      cross-tier score fidelity run the destination tier on 'custom'.
    """
    if dst.is_custom:
        return scores
    return None


# =============================================================================
# State / result types
# =============================================================================


class TieredState(NamedTuple):
    """Both tiers' states as one pytree (the checkpoint/shard_map leaf set)."""

    hot: HKVState
    cold: HKVState


class TieredFind(NamedTuple):
    table: "TieredHKVTable"   # successor (promotion mutates the hierarchy)
    values: jax.Array         # [N, dim] — zeros where neither tier holds the key
    found: jax.Array          # bool [N] — present in EITHER tier
    hot_hit: jax.Array        # bool [N] — served from the hot tier
    promoted: jax.Array       # int32 — cold hits re-admitted into hot
    demoted: jax.Array        # int32 — hot victims cascaded into cold
    dropped: jax.Array        # int32 — UPPER BOUND on pairs that left the
                              #   hierarchy: cold-tier rejections + cold
                              #   evictions (an evicted cold copy may be an
                              #   inclusive duplicate whose hot copy lives
                              #   on — never an undercount; DESIGN §2.5)


class TieredUpsert(NamedTuple):
    table: "TieredHKVTable"
    status: jax.Array         # int8 [N] — hot-tier merge status codes
    demoted: jax.Array        # int32 — pairs handed down to the cold tier
    dropped: jax.Array        # int32 — upper bound on hierarchy exits (see
                              #   TieredFind.dropped)
    # bool [N] — key present SOMEWHERE in the hierarchy after the op:
    # admitted by the hot tier, or hot-rejected and actually absorbed by
    # the cold tier (its per-lane verdict, not an assumption).
    ok: jax.Array


class TieredFindOrInsert(NamedTuple):
    table: "TieredHKVTable"
    values: jax.Array         # [N, dim] — stored row (either tier) or init
    found: jax.Array          # bool [N] — existed in EITHER tier before the op
    status: jax.Array         # int8 [N] — hot-tier merge status codes
    promoted: jax.Array       # int32
    demoted: jax.Array        # int32
    dropped: jax.Array        # int32
    ok: jax.Array             # bool [N] — key resident SOMEWHERE after the op


class _DemoteResult(NamedTuple):
    cold: HKVTable
    demoted: jax.Array        # int32 — pairs upserted into the cold tier
    dropped: jax.Array        # int32 — pairs lost at the cold boundary
    placed: jax.Array         # bool [N] — lane's pair is now cold-resident


class TieredDemote(NamedTuple):
    table: "TieredHKVTable"
    demoted: jax.Array        # int32 — pairs the cold tier absorbed
    dropped: jax.Array        # int32 — pairs lost at the cold boundary


class TieredSweep(NamedTuple):
    table: "TieredHKVTable"
    swept: jax.Array          # int32 — entries removed across BOTH tiers
                              #   (inclusive hot/cold copies count twice —
                              #   both slots were freed)


class TieredEvictIf(NamedTuple):
    table: "TieredHKVTable"
    evicted: EvictionStream   # 2*budget lanes: hot stream then cold
                              #   stream, stale inclusive cold copies
                              #   masked out (hot copy authoritative)
    count: jax.Array          # int32 — live lanes in the stream


# =============================================================================
# The handle
# =============================================================================


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class TieredHKVTable:
    """Two-tier HKV hierarchy behind the same handle discipline as
    `HKVTable`: the two tier handles are the pytree children (their states
    are the leaves; both cfgs ride as static aux), so a tiered handle
    jits, donates, scans, and checkpoints exactly like a flat one.

        table = TieredHKVTable.create(
            hot_capacity=8 * 128, cold_capacity=64 * 128, dim=32)
        res = table.insert_or_assign(keys, values)  # res.table, res.status
        out = res.table.find(keys)                  # out.table carries the
                                                    # promotion's effects

    `promote_on_find=False` makes `find` a pure reader (no re-admission);
    the default promotes, which is what makes the hot tier track the
    access distribution.
    """

    hot: HKVTable
    cold: HKVTable
    promote_on_find: bool = True

    # -- pytree protocol -----------------------------------------------------

    def tree_flatten(self):
        return (self.hot, self.cold), (self.promote_on_find,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        hot, cold = children
        return cls(hot=hot, cold=cold, promote_on_find=aux[0])

    # -- construction --------------------------------------------------------

    @classmethod
    def create(cls, *, hot_capacity: int, cold_capacity: int, dim: int,
               score_policy: str = "lru",
               cold_score_policy: str = "custom",
               cold_value_tier: str = "hmem",
               promote_on_find: bool = True,
               backend: str = "auto",
               **shared_cfg) -> "TieredHKVTable":
        """Allocate both tiers.  Value-plane geometry (dim, aux columns,
        dtype, slots per bucket) is shared — rows must move between tiers
        without reshaping; capacities and score policies are per-tier.

        The cold tier defaults to the 'custom' policy so demoted pairs
        carry their translated hot-tier scores (see `translate_scores`),
        and to the 'hmem' value placement (§3.6): host-capacity values,
        HBM key-side processing in both tiers.
        """
        hot_cfg = HKVConfig(capacity=hot_capacity, dim=dim,
                            score_policy=score_policy, **shared_cfg)
        cold_cfg = HKVConfig(capacity=cold_capacity, dim=dim,
                             score_policy=cold_score_policy,
                             value_tier=cold_value_tier, **shared_cfg)
        return cls.from_configs(hot_cfg, cold_cfg,
                                promote_on_find=promote_on_find,
                                backend=backend)

    @classmethod
    def from_configs(cls, hot_cfg: HKVConfig, cold_cfg: HKVConfig, *,
                     promote_on_find: bool = True,
                     backend: str = "auto") -> "TieredHKVTable":
        if hot_cfg.total_value_dim != cold_cfg.total_value_dim or (
                hot_cfg.value_dtype != cold_cfg.value_dtype):
            raise ValueError(
                "hot/cold tiers must share value-row geometry; got "
                f"{hot_cfg.total_value_dim}x{hot_cfg.value_dtype} vs "
                f"{cold_cfg.total_value_dim}x{cold_cfg.value_dtype}"
            )
        return cls(hot=HKVTable.create(hot_cfg, backend=backend),
                   cold=HKVTable.create(cold_cfg, backend=backend),
                   promote_on_find=promote_on_find)

    @classmethod
    def wrap(cls, state: TieredState, hot_cfg: HKVConfig,
             cold_cfg: HKVConfig, *, promote_on_find: bool = True,
             backend: str = "auto") -> "TieredHKVTable":
        """Bind existing tier states (e.g. shard-local under shard_map)."""
        return cls(hot=HKVTable.wrap(state.hot, hot_cfg, backend=backend),
                   cold=HKVTable.wrap(state.cold, cold_cfg, backend=backend),
                   promote_on_find=promote_on_find)

    # -- views ---------------------------------------------------------------

    @property
    def state(self) -> TieredState:
        return TieredState(hot=self.hot.state, cold=self.cold.state)

    def with_state(self, state: TieredState) -> "TieredHKVTable":
        return dataclasses.replace(
            self, hot=self.hot.with_state(state.hot),
            cold=self.cold.with_state(state.cold))

    def with_tiers(self, hot: HKVTable, cold: HKVTable) -> "TieredHKVTable":
        return dataclasses.replace(self, hot=hot, cold=cold)

    @property
    def capacity(self) -> int:
        return self.hot.capacity + self.cold.capacity

    @property
    def hot_fraction(self) -> float:
        return self.hot.capacity / self.capacity

    @property
    def dim(self) -> int:
        return self.hot.dim

    def keys(self, keys: Any) -> U64:
        return normalize_keys(keys)

    # -- readers -------------------------------------------------------------

    def contains(self, keys: Any, *, telemetry=None) -> jax.Array:
        """Pure reader: membership in either tier (never promotes)."""
        k = normalize_keys(keys)
        in_hot = self.hot.contains(k, telemetry=telemetry)
        return in_hot | self.cold.contains(_mask_keys(k, ~in_hot),
                                           telemetry=telemetry)

    def size(self) -> jax.Array:
        """Distinct live keys across the hierarchy.  Inclusive-on-access
        duplicates (a promoted key's cold copy) are counted ONCE — the
        hot key plane is probed against the cold tier, which is a
        capacity-sized membership scan; `size` is a diagnostic op, not a
        hot-path one."""
        hs = self.hot.state
        hot_keys = U64(hs.key_hi.reshape(-1), hs.key_lo.reshape(-1))
        dup = self.cold.contains(hot_keys) & ~u64.is_empty(hot_keys)
        return (self.hot.size() + self.cold.size()
                - jnp.sum(dup.astype(jnp.int32)))

    def load_factor(self) -> jax.Array:
        return self.size().astype(jnp.float32) / float(self.capacity)

    @property
    def num_buckets(self) -> int:
        """Export-space bucket count: hot buckets first, then cold —
        `export_batch` iterates one concatenated bucket index space."""
        return self.hot.num_buckets + self.cold.num_buckets

    def export_batch(self, bucket_start: int,
                     bucket_count: int) -> ops_mod.ExportResult:
        """Stream a contiguous range of the CONCATENATED bucket space
        (hot buckets [0, H), cold buckets [H, H+C)) — the checkpoint /
        publisher-delta read path.

        Inclusive-on-access duplicates are resolved in the hot tier's
        favor: a cold entry whose key is hot-resident is masked out, since
        its cold copy may be stale (write-back only freshens it on
        demotion, DESIGN.md §2.5).  The extra hot membership probe is a
        checkpoint-path cost, not a hot-path one."""
        hot_b = self.hot.num_buckets
        end = bucket_start + bucket_count
        parts = []
        if bucket_start < hot_b:
            parts.append(self.hot.export_batch(
                bucket_start, min(end, hot_b) - bucket_start))
        if end > hot_b:
            c0 = max(bucket_start - hot_b, 0)
            c = self.cold.export_batch(c0, end - hot_b - c0)
            dup = self.hot.contains(U64(c.key_hi, c.key_lo))
            parts.append(c._replace(mask=c.mask & ~dup))
        if len(parts) == 1:
            return parts[0]
        h, c = parts
        return ops_mod.ExportResult(*[
            jnp.concatenate([a, b]) for a, b in zip(h, c)
        ])

    # -- the demotion cascade ------------------------------------------------

    def _demote(self, cold: HKVTable, keys: U64, values: jax.Array,
                scores: U64, mask: jax.Array) -> _DemoteResult:
        """Upsert displaced pairs into the cold tier; count what it keeps
        and what leaves the hierarchy at its boundary.

        `keys/values/scores` are full lanes with `mask` selecting the
        live pairs (EvictionStream layout); masked-out lanes become the
        EMPTY sentinel, which every table op ignores.
        """
        mk = _mask_keys(keys, mask)
        cs = translate_scores(self.hot.cfg.policy, cold.cfg.policy,
                              scores)
        res = ops_mod.insert_and_evict(
            cold.state, cold.cfg, mk, values,
            custom_scores=cs, backend=cold.backend,
        )
        placed = mask & (res.status != ops_mod.STATUS_REJECTED)
        demoted = jnp.sum(placed.astype(jnp.int32))
        # losses at the cold boundary: rejected demotions + the cold
        # tier's own evictions (pairs pushed out of the last tier)
        dropped = (jnp.sum((mask & ~placed).astype(jnp.int32))
                   + res.evicted.count().astype(jnp.int32))
        return _DemoteResult(cold=cold.with_state(res.state),
                             demoted=demoted, dropped=dropped, placed=placed)

    def _demote_stream(self, cold: HKVTable,
                       stream: EvictionStream) -> _DemoteResult:
        return self._demote(cold, stream.keys, stream.values,
                            stream.scores, stream.mask)

    def demote(self, stream: EvictionStream) -> TieredDemote:
        """Hand a stream of (key, value, score) pairs down into the cold
        tier — the PUBLIC form of the demotion cascade (scores translated
        across the per-tier policies, losses at the cold boundary
        counted).  The maintenance rebalancer feeds `evict_if`'s hot-tier
        stream through here (repro.maintenance.rebalance)."""
        dem = self._demote_stream(self.cold, stream)
        return TieredDemote(table=self.with_tiers(self.hot, dem.cold),
                            demoted=dem.demoted, dropped=dem.dropped)

    # -- inserters -----------------------------------------------------------

    def insert_or_assign(self, keys: Any, values: jax.Array,
                         custom_scores: Optional[Any] = None, *,
                         telemetry=None) -> TieredUpsert:
        """Upsert into the hot tier; displaced pairs — victims evicted by
        admission AND incoming pairs the hot tier rejected — cascade into
        the cold tier.  `status` reports the hot tier's verdict; `.ok`
        also covers hot-rejected pairs absorbed by the cold tier."""
        k = normalize_keys(keys)
        cs = _opt_keys(custom_scores)
        values = ops_mod.pad_rows(values, self.hot.cfg)
        res = ops_mod.insert_and_evict(
            self.hot.state, self.hot.cfg, k, values,
            custom_scores=cs, backend=self.hot.backend, telemetry=telemetry,
        )
        hot = self.hot.with_state(res.state)
        first, rep_orig = _dedupe_lanes(k)
        dk, dv, ds, dm = self._displaced(k, values, res, rej_custom=cs,
                                         first=first)
        dem = self._demote(self.cold, dk, dv, ds, dm)
        if telemetry is not None:
            telemetry.record("tier", ops_mod._obs().tier_motion(
                demoted=dem.demoted, dropped=dem.dropped))
        return TieredUpsert(
            table=self.with_tiers(hot, dem.cold), status=res.status,
            demoted=dem.demoted, dropped=dem.dropped,
            ok=_hierarchy_ok(res.status, dem.placed, rep_orig),
        )

    def find_or_insert(self, keys: Any, init_values: jax.Array,
                       custom_scores: Optional[Any] = None, *,
                       telemetry=None) -> TieredFindOrInsert:
        """The training-path op: lookup across the hierarchy, admit
        misses, promote cold hits.

        `custom_scores` feeds the HOT tier's admission (meaningful under
        its 'custom' policy — the delta-ingest path; other policies stamp
        their own).  Caller scores apply to every lane, including
        promoted cold hits.

        Per key: hot hit -> stored hot row (scores touched).  Hot miss
        but cold hit -> the cold row is re-admitted into the hot tier
        (promotion) and returned.  Miss in both -> `init_values` row is
        admitted into the hot tier.  Every hot-tier displacement — victims
        of admission and rejected incoming pairs alike — cascades into
        the cold tier, so admission-rejected NEW keys land cold-side
        rather than vanishing (reported via `status` = REJECTED and the
        conservation counters).
        """
        k = normalize_keys(keys)
        cs = _opt_keys(custom_scores)
        # ONE hot probe: shared with the upsert closure through the PR-2
        # loc= seam (locate output depends only on the key plane, which
        # the cold reads below never touch)
        pre = self.hot.find_ptr(k)
        hot_pre = pre.found
        # probe the cold tier only for hot misses: full-width rows so aux
        # optimizer columns travel with a promoted embedding
        cold_rows = self.cold.find_rows(_mask_keys(k, ~hot_pre))
        cold_hit = cold_rows.found
        init_full = ops_mod.pad_rows(init_values, self.hot.cfg)
        admit_rows = jnp.where(cold_hit[:, None], cold_rows.rows, init_full)
        res = ops_mod.find_or_insert(
            self.hot.state, self.hot.cfg, k, admit_rows, custom_scores=cs,
            backend=self.hot.backend, return_evicted=True, loc=pre,
            telemetry=telemetry,
        )
        hot = self.hot.with_state(res.state)
        first, rep_orig = _dedupe_lanes(k)
        # rejected COLD HITS stay where they are: the pair never left the
        # cold tier, and re-demoting it would overwrite its accumulated
        # cold score with a fresh count-1 init (each rejected re-access
        # would make the key MORE evictable — exactly backwards)
        dk, dv, ds, dm = self._displaced(k, admit_rows, res, rej_custom=cs,
                                         first=first,
                                         already_cold=cold_hit)
        dem = self._demote(self.cold, dk, dv, ds, dm)
        promoted = jnp.sum((cold_hit & first
                            & (res.status >= ops_mod.STATUS_UPDATED)
                            & (res.status <= ops_mod.STATUS_EVICTED))
                           .astype(jnp.int32))
        if telemetry is not None:
            telemetry.record("tier", ops_mod._obs().tier_motion(
                promoted=promoted, demoted=dem.demoted, dropped=dem.dropped))
        return TieredFindOrInsert(
            table=self.with_tiers(hot, dem.cold),
            values=res.values,
            found=hot_pre | cold_hit,
            status=res.status,
            promoted=promoted,
            demoted=dem.demoted, dropped=dem.dropped,
            # rejected cold hits never left the cold tier: resident by
            # definition, without appearing in the demotion batch
            ok=(_hierarchy_ok(res.status, dem.placed, rep_orig)
                | ((res.status == ops_mod.STATUS_REJECTED) & cold_hit)),
        )

    def _displaced(self, k: U64, values: jax.Array, res,
                   rej_custom: Optional[U64] = None,
                   first: Optional[jax.Array] = None,
                   already_cold: Optional[jax.Array] = None):
        """Merge the eviction stream with hot-REJECTED incoming pairs into
        one positionally-aligned demotion batch.

        A lane either evicted a victim (admission succeeded) or was
        rejected — never both — so victim and rejected-incoming lanes are
        disjoint; and a rejected key cannot equal any victim key (a
        hot-resident key would have been a hit, not a rejection).

        Rejected incoming pairs carry their would-be admission score: the
        caller's custom score under the 'custom' policy, else a fresh
        hot-policy init score at the post-op clock (for LFU-family
        policies the within-batch multiplicity collapses to 1 — the
        demotion path's documented approximation, DESIGN.md §2.5).
        `already_cold` lanes are excluded: their pair never left the cold
        tier, so there is nothing to hand down.
        """
        st = res.evicted
        rej = (res.status == ops_mod.STATUS_REJECTED) & ~st.mask
        if already_cold is not None:
            rej &= ~already_cold
        # dedupe rejected lanes: only each key's first lane demotes (the
        # upsert closure already collapsed duplicates to one verdict)
        rej &= _first_occurrence(k) if first is None else first
        policy = self.hot.cfg.policy
        if policy.is_custom:
            rej_sc = rej_custom  # the hot upsert itself required these
        else:
            hs = res.state
            rej_sc = policy.init_score(
                U64(hs.clock_hi, hs.clock_lo), hs.epoch,
                jnp.ones(rej.shape, jnp.uint32), None, rej.shape,
            )
        keys = U64(jnp.where(st.mask, st.key_hi, k.hi),
                   jnp.where(st.mask, st.key_lo, k.lo))
        vals = jnp.where(st.mask[:, None], st.values,
                         values.astype(st.values.dtype))
        scores = U64(jnp.where(st.mask, st.score_hi, rej_sc.hi),
                     jnp.where(st.mask, st.score_lo, rej_sc.lo))
        return keys, vals, scores, st.mask | rej

    def ingest(self, keys: Any, init_values: jax.Array,
               custom_scores: Optional[Any] = None, *,
               telemetry=None) -> TieredUpsert:
        """Deferred-structural admit (the overlapped-ingest schedule):
        find_or_insert without the value readback.  Runs the FULL
        hierarchy motion — a cold-resident key must be PROMOTED, not
        shadowed by a fresh init row in hot (which would hide its trained
        value from every later read).  The readback is dead code XLA
        eliminates under jit."""
        r = self.find_or_insert(keys, init_values,
                                custom_scores=custom_scores,
                                telemetry=telemetry)
        return TieredUpsert(table=r.table, status=r.status,
                            demoted=r.demoted, dropped=r.dropped, ok=r.ok)

    # -- find with miss-path promotion ----------------------------------------

    def find(self, keys: Any, *, promote: Optional[bool] = None,
             telemetry=None) -> TieredFind:
        """Hierarchy lookup.  Hot misses probe the cold tier; cold hits
        are re-admitted into the hot tier (unless promotion is off), whose
        displaced victims cascade back down — the inclusive-on-access
        cache motion.  The read values are the pre-promotion rows either
        way (promotion never changes what this call returns, only where
        the NEXT access finds it)."""
        if promote is None:
            promote = self.promote_on_find
        k = normalize_keys(keys)
        # both probe legs go through the handle readers, so on the kernel
        # backend each is ONE fused find_scan pass (hot: values in-line;
        # cold hmem values cross tiers via the locate+tier_gather split)
        h = self.hot.find(k, telemetry=telemetry)
        cold_rows = self.cold.find_rows(_mask_keys(k, ~h.found),
                                        telemetry=telemetry)
        cold_hit = cold_rows.found
        values = jnp.where(h.found[:, None], h.values,
                           cold_rows.rows[:, : self.dim].astype(h.values.dtype))
        found = h.found | cold_hit
        zero = jnp.zeros((), jnp.int32)
        if not promote:
            return TieredFind(table=self, values=values, found=found,
                              hot_hit=h.found, promoted=zero, demoted=zero,
                              dropped=zero)
        # re-admit cold hits (first occurrence only: duplicates collapse),
        # carrying their cold scores across the policy translation.  Every
        # promoted key is a known hot MISS, so the closure's locate is
        # supplied as all-miss through the loc= seam — no extra probe.
        pk = _mask_keys(k, cold_hit & _first_occurrence(k))
        cs = translate_scores(self.cold.cfg.policy, self.hot.cfg.policy,
                              U64(cold_rows.score_hi, cold_rows.score_lo))
        n = pk.hi.shape[0]
        all_miss = find_mod.Locate(
            found=jnp.zeros((n,), bool),
            bucket=jnp.zeros((n,), jnp.int32),
            slot=jnp.zeros((n,), jnp.int32),
            row=jnp.zeros((n,), jnp.int32),
        )
        res = ops_mod.insert_and_evict(
            self.hot.state, self.hot.cfg, pk, cold_rows.rows,
            custom_scores=cs, backend=self.hot.backend, loc=all_miss,
        )
        hot = self.hot.with_state(res.state)
        dem = self._demote_stream(self.cold, res.evicted)
        promoted = jnp.sum(
            ((res.status == ops_mod.STATUS_INSERTED)
             | (res.status == ops_mod.STATUS_EVICTED)).astype(jnp.int32))
        if telemetry is not None:
            telemetry.record("tier", ops_mod._obs().tier_motion(
                promoted=promoted, demoted=dem.demoted, dropped=dem.dropped))
        return TieredFind(
            table=self.with_tiers(hot, dem.cold), values=values, found=found,
            hot_hit=h.found, promoted=promoted, demoted=dem.demoted,
            dropped=dem.dropped,
        )

    # -- updaters / sessions ---------------------------------------------------

    def assign(self, keys: Any, values: jax.Array,
               update_scores: bool = False) -> "TieredHKVTable":
        """Updater on the HOT tier only: in a promote-on-access hierarchy
        every trained/served row was just promoted, so hot-resident rows
        are exactly the writable set (cold copies refresh via write-back
        on demotion)."""
        return dataclasses.replace(
            self, hot=self.hot.assign(keys, values,
                                      update_scores=update_scores))

    def erase(self, keys: Any, *, telemetry=None) -> "TieredHKVTable":
        """Structural: remove keys from BOTH tiers (an inclusive-cache
        erase must kill the cold copy too or the key would resurrect on
        the next miss)."""
        return self.with_tiers(self.hot.erase(keys, telemetry=telemetry),
                               self.cold.erase(keys, telemetry=telemetry))

    def clear(self) -> "TieredHKVTable":
        return self.with_tiers(self.hot.clear(), self.cold.clear())

    # -- maintenance (predicated sweeps + observability; DESIGN.md
    # §Maintenance) -----------------------------------------------------------

    def erase_if(self, pred, *, telemetry=None) -> TieredSweep:
        """Structural sweep of BOTH tiers: like `erase`, an inclusive-cache
        removal must kill the cold copy too, or an expired key would
        resurrect on the next miss.  Works for TTL expiry on the default
        tier policies because demoted scores are translated verbatim into
        the cold tier's 'custom' domain — the epoch plane survives the
        crossing (`translate_scores`)."""
        hr = self.hot.erase_if(pred, telemetry=telemetry)
        cr = self.cold.erase_if(pred, telemetry=telemetry)
        return TieredSweep(table=self.with_tiers(hr.table, cr.table),
                           swept=hr.swept + cr.swept)

    def evict_if(self, pred, budget: int, *,
                 telemetry=None) -> TieredEvictIf:
        """Remove up to `budget` matching entries per tier, coldest first,
        returning them as one concatenated stream (hot lanes first).  An
        evicted entry leaves the WHOLE hierarchy: a hot-evicted key's
        stale inclusive cold copy is erased with it (same no-resurrection
        rule as `erase`/`erase_if` — the stream must not report a key
        gone while a cold hit could still serve it), and a cold lane
        whose key remains hot-resident is a stale inclusive copy whose
        slot is freed but whose lane is masked out of the stream (the hot
        copy is authoritative — same rule as `export_batch`)."""
        hr = ops_mod.evict_if(self.hot.state, self.hot.cfg, pred, budget,
                              backend=self.hot.backend, telemetry=telemetry)
        cr = ops_mod.evict_if(self.cold.state, self.cold.cfg, pred, budget,
                              backend=self.cold.backend, telemetry=telemetry)
        dup = self.hot.contains(cr.evicted.masked_keys())  # pre-sweep hot
        cmask = cr.evicted.mask & ~dup
        # hot-evicted keys: kill any surviving stale cold copy (the cold
        # sweep's own budget/rank order may not have reached it)
        cold_state = ops_mod.erase(cr.state, self.cold.cfg,
                                   hr.evicted.masked_keys())
        stream = EvictionStream(*[
            jnp.concatenate([getattr(hr.evicted, f),
                             getattr(cr.evicted, f)])
            for f in ("key_hi", "key_lo", "values", "score_hi", "score_lo")
        ], mask=jnp.concatenate([hr.evicted.mask, cmask]))
        return TieredEvictIf(
            table=self.with_tiers(self.hot.with_state(hr.state),
                                  self.cold.with_state(cold_state)),
            evicted=stream,
            count=hr.count + jnp.sum(cmask.astype(jnp.int32)),
        )

    def stats(self):
        """Hierarchy-level `TableStats`: histograms summed, size deduped
        across inclusive copies (== `size()`); per-tier detail via
        `tier_stats()`."""
        from repro.maintenance import stats as stats_mod  # deferred: layering

        hot, cold = self.tier_stats()
        return stats_mod.combine_stats(hot, cold, size=self.size())

    def tier_stats(self):
        """(hot TableStats, cold TableStats) — the per-tier load factors
        the watermark rebalancer and capacity planning read."""
        return self.hot.stats(), self.cold.stats()

    @property
    def epoch(self) -> jax.Array:
        return self.hot.epoch

    def set_epoch(self, epoch: Any) -> "TieredHKVTable":
        """Stamp the application epoch on BOTH tiers (one TTL clock for
        the whole hierarchy)."""
        return self.with_tiers(self.hot.set_epoch(epoch),
                               self.cold.set_epoch(epoch))

    def session(self) -> "TieredSession":
        """Role-aware op session over the HOT TIER ONLY (the writable
        set — see `assign`); `commit()` returns the tiered successor
        handle.  Session reads are hot-scoped: `s.find(k)` misses a
        cold-resident key that `table.find(k)` would hit — use the table
        surface for hierarchy-wide reads."""
        return TieredSession(self)


class TieredSession:
    """`OpSession` proxy over the HOT tier: records reader/updater/
    inserter ops against it and rebinds the tiered handle on commit.

    Scope contract (deliberate, documented at `TieredHKVTable.session`):
    ops see ONLY the hot tier.  That is exactly right for the session's
    consumer — the fused read-modify-write gradient path, whose keys were
    just promoted by their own lookup — and exactly wrong for hierarchy-
    wide reads, which belong on the table surface (`find`/`contains`).
    Within that scope, PR 2's fusion guarantees hold unchanged (shared
    locates are exact; inserters serialize)."""

    def __init__(self, table: TieredHKVTable):
        self._table = table
        self._inner = table.hot.session()

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def commit(self) -> TieredHKVTable:
        hot = self._inner.commit()
        return dataclasses.replace(self._table, hot=hot)


# =============================================================================
# helpers
# =============================================================================


def _mask_keys(keys: U64, keep: jax.Array) -> U64:
    """EMPTY-sentinel out the lanes where ~keep (every op ignores them)."""
    return U64(jnp.where(keep, keys.hi, jnp.uint32(u64.EMPTY_HI)),
               jnp.where(keep, keys.lo, jnp.uint32(u64.EMPTY_LO)))


def _dedupe_lanes(keys: U64):
    """(first, rep_orig) over the batch: `first[i]` — lane i is its key's
    first occurrence (EMPTY lanes excluded); `rep_orig[i]` — the original
    position of lane i's group representative (maps a per-rep verdict back
    onto every duplicate lane)."""
    from repro.core import merge as merge_mod

    d = merge_mod.dedupe_keys(keys)
    n = keys.hi.shape[0]
    first = jnp.zeros((n,), bool).at[
        jnp.where(d.rep_mask, d.idx_sorted, n)
    ].set(True, mode="drop")
    return first, d.idx_sorted[d.inverse]


def _first_occurrence(keys: U64) -> jax.Array:
    return _dedupe_lanes(keys)[0]


def _hierarchy_ok(status: jax.Array, placed: jax.Array,
                  rep_orig: jax.Array) -> jax.Array:
    """Per-lane residency after an upsert: admitted by the hot tier, or
    hot-rejected with the demotion actually PLACED by the cold tier (its
    verdict lives at the group representative's lane — duplicates map to
    it through `rep_orig`)."""
    hot_ok = (status >= ops_mod.STATUS_UPDATED) & (
        status <= ops_mod.STATUS_EVICTED
    )
    return hot_ok | ((status == ops_mod.STATUS_REJECTED) & placed[rep_orig])
