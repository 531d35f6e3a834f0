"""HKV-backed dynamic embedding — the paper's cache-semantic table wired
into a model input layer (the HugeCTR/TFRA integration pattern, §1/§6).

Training path (one step):
  1. find_or_insert on the (flattened) token batch — INSERTER role, the
     step's single structural op.  New tokens are admitted subject to
     score-based admission control; at λ=1.0 the table stays full and
     low-value embeddings are evicted in place (continuous online
     ingestion, paper Fig. 2).
  2. The model consumes the gathered rows; jax.grad gives d(loss)/d(rows).
  3. apply_grads — UPDATER role: per-unique-token gradient sums feed a
     sparse optimizer whose slot state lives in aux value columns, handed
     to the table as a structured `ops.RowUpdate` session op — on the
     kernel backend the whole step is ONE fused update_scan launch (probe
     + in-kernel optimizer apply + masked write-back; §3.5 adaptation).

Serving path: `find` only — READER role; unseen tokens fall back to the
same deterministic hash-derived init the training path would insert, so
train/serve disagree only by the not-yet-applied gradients.

All table traffic goes through the `HKVTable` handle (`repro.core.api`);
this module owns only token↔key derivation and the optimizer hookup.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ops as ops_mod
from repro.core import roles
from repro.core import u64
from repro.core.api import HKVTable, dedupe_keys
from repro.core.table import HKVConfig
from repro.core.tiered import TieredHKVTable, TieredState
from repro.core.u64 import U64
from repro.embedding.sparse_opt import SparseOptimizer


@dataclasses.dataclass(frozen=True)
class HKVEmbedding:
    capacity: int                      # table slots (decoupled from key-space size!)
    dim: int
    optimizer: SparseOptimizer = SparseOptimizer("rowwise_adagrad")
    buckets_per_key: int = 2           # dual-bucket: §3.4 retention + utilization
    score_policy: str = "lru"
    value_dtype: jnp.dtype = jnp.float32
    value_tier: str = "hbm"
    backend: str = "auto"              # inserter backend: 'auto'|'jnp'|'kernel' (DESIGN.md §4)
    # Tier hierarchy (DESIGN.md §2.5): when `hot_capacity` is set the
    # embedding runs a TieredHKVTable — an HBM hot tier of `hot_capacity`
    # slots in front of a `capacity`-slot cold tier whose value plane uses
    # `cold_value_tier` placement.  The embedding contract is unchanged;
    # the table requirement relaxes from "fits in HBM" to "hot set fits".
    hot_capacity: Optional[int] = None
    cold_score_policy: str = "custom"  # demoted pairs keep translated scores
    cold_value_tier: str = "hmem"

    @property
    def is_tiered(self) -> bool:
        return self.hot_capacity is not None

    @property
    def total_capacity(self) -> int:
        return self.capacity + (self.hot_capacity or 0)

    def config(self) -> HKVConfig:
        """The flat table's config — the HOT tier's when tiered (capacity
        is the only field that differs between the two uses)."""
        return HKVConfig(
            capacity=self.hot_capacity if self.is_tiered else self.capacity,
            dim=self.dim,
            buckets_per_key=self.buckets_per_key,
            score_policy=self.score_policy,
            value_dtype=self.value_dtype,
            value_tier=self.value_tier,
            aux_value_dim=self.optimizer.aux_dim(self.dim),
        )

    def cold_config(self) -> HKVConfig:
        return dataclasses.replace(
            self.config(), capacity=self.capacity,
            score_policy=self.cold_score_policy,
            value_tier=self.cold_value_tier,
        )

    def create(self):
        if self.is_tiered:
            return TieredHKVTable.from_configs(
                self.config(), self.cold_config(), backend=self.backend)
        return HKVTable.create(self.config(), backend=self.backend)

    def wrap(self, state):
        """Re-bind a (shard-local) state with the right handle type — the
        one entry point shard_map bodies use, so the distributed layer is
        agnostic to flat-vs-tiered."""
        if self.is_tiered:
            return TieredHKVTable.wrap(
                TieredState(*state) if not isinstance(state, TieredState)
                else state,
                self.config(), self.cold_config(), backend=self.backend)
        return HKVTable.wrap(state, self.config(), backend=self.backend)

    # -- key & init derivation -------------------------------------------------

    def keys_of(self, tokens: jax.Array) -> U64:
        """Token ids -> u64 keys. Negative ids (padding) become the EMPTY
        sentinel and are ignored by every table op."""
        t = tokens.reshape(-1)
        neg = t < 0
        return U64(
            jnp.where(neg, jnp.uint32(u64.EMPTY_HI), jnp.uint32(0)),
            jnp.where(neg, jnp.uint32(u64.EMPTY_LO), t.astype(jnp.uint32)),
        )

    def default_rows(self, keys: U64) -> jax.Array:
        """Deterministic per-key init: counter-mode fmix32 bits -> uniform
        rows in ±1/sqrt(dim).  Restart-stable and identical on every shard."""
        h1, _ = u64.hash_pair(keys)
        col_salt = (
            jnp.arange(self.dim, dtype=jnp.uint32) * jnp.uint32(0x9E3779B9)
        ) ^ jnp.uint32(0x85EBCA6B)
        bits = u64.fmix32(h1[:, None] ^ col_salt[None, :])
        uni = bits.astype(jnp.float32) * (1.0 / 4294967296.0)
        return ((uni - 0.5) * (2.0 / np.sqrt(self.dim))).astype(self.value_dtype)

    # -- roles -------------------------------------------------------------

    @roles.scoped("hkv.emb.lookup_train")
    def lookup_train(self, table: HKVTable, tokens: jax.Array):
        """INSERTER: find_or_insert the token batch. Returns (table, rows)."""
        keys = self.keys_of(tokens)
        res = table.find_or_insert(keys, self.default_rows(keys))
        emb = res.values.reshape(tokens.shape + (self.dim,))
        return res.table, emb

    @roles.scoped("hkv.emb.lookup_serve")
    def lookup_serve(self, table, tokens: jax.Array) -> jax.Array:
        """READER: find; misses fall back to the deterministic init row.

        On a tiered table this is the PURE-READER form (promote=False):
        the serve path discards the successor handle, so promotion work
        would be two structural upserts thrown away per lookup."""
        keys = self.keys_of(tokens)
        if isinstance(table, TieredHKVTable):
            res = table.find(keys, promote=False)
        else:
            res = table.find(keys)
        vals = jnp.where(res.found[:, None], res.values, self.default_rows(keys))
        return vals.reshape(tokens.shape + (self.dim,))

    @roles.scoped("hkv.emb.apply_grads")
    def apply_grads(
        self, table: HKVTable, tokens: jax.Array, grads: jax.Array
    ) -> HKVTable:
        """UPDATER: sum grads per unique token, hand the table the
        structured gradient step (`ops.RowUpdate`) — so the whole update is
        dedupe (XLA) + ONE table op, and on backend='kernel' ONE fused
        update_scan launch (probe + optimizer apply + write-back).

        Dedupe is COMPACTED: group g's representative key lands at slot g,
        so the unique keys occupy a prefix (EMPTY-padded beyond) and the
        segment sums are already aligned with them — the old form
        re-broadcast the sums to every sorted slot (`g_sum[d.gid]`, a
        second batch-sized [N, dim] buffer) to line up with the
        sorted-space `d.unique`."""
        keys = self.keys_of(tokens)
        g = grads.reshape(-1, self.dim)
        n = g.shape[0]
        d = dedupe_keys(keys)
        uniq = U64(
            jnp.full((n,), u64.EMPTY_HI, jnp.uint32)
            .at[d.gid].set(keys.hi[d.idx_sorted]),
            jnp.full((n,), u64.EMPTY_LO, jnp.uint32)
            .at[d.gid].set(keys.lo[d.idx_sorted]),
        )
        g_sum = jax.ops.segment_sum(g[d.idx_sorted], d.gid, num_segments=n,
                                    indices_are_sorted=True)
        s = table.session()
        # rejected-admission tokens simply have no row to update (cache
        # semantics: un-admitted embeddings do not train)
        s.update_rows(uniq, ops_mod.RowUpdate(self.optimizer, g_sum))
        return s.commit()

    def ingest(self, table: HKVTable, tokens: jax.Array) -> HKVTable:
        """Deferred-structural variant: admit this batch's new tokens without
        reading values (used by the overlapped-ingest schedule, §3.5/Exp#3e)."""
        keys = self.keys_of(tokens)
        return table.ingest(keys, self.default_rows(keys)).table
