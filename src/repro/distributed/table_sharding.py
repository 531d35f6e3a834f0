"""Distributed HKV table: all-to-all key routing over the device mesh.

The paper delegates multi-GPU sharding to application code (§7); this
module IS that application layer, built the way HugeCTR shards
model-parallel embeddings — and it is the piece that makes the multi-pod
dry-run meaningful for the technique:

  * Every shard owns an independent local HKV table of capacity/n_shards
    (its own buckets, digests, scores, values — all core invariants hold
    locally, including cache semantics at local λ=1.0).
  * A key's OWNER shard is a hash of the key (fmix of h2), so hot Zipfian
    keys scatter uniformly across shards.
  * Lookup/ingest: local dedupe -> capacity-bounded all_to_all of keys to
    owners -> owner-side find_or_insert -> all_to_all of value rows back.
    Wire cost per unique token: 8 B of key out, dim x 4 B of row back —
    strictly less than a vocab-parallel all-reduce at model-axis >= 2.
  * Gradients: the same routing in reverse (updater role).  Each unique
    key's grad is summed locally, routed to its single owner, then
    owner-side deduped across sources and applied ONCE via the sparse
    optimizer — no replica divergence, because no replicas exist.
  * Admission/eviction happen owner-side with unchanged semantics.

Skew handling: per-destination capacity = factor x fair share.  Uniques
beyond capacity fall back to deterministic init rows and are counted in
the returned `overflow` metric (they retry next step; a recurring hot key
is admitted on its next occurrence).

Surfaces (DESIGN.md §API layer): `ShardedHKVEmbedding` is the shard_map
engine (raw HKVState in/out — the form shard_map specs want); the
`ShardedHKVTable` handle on top implements the same `KVTable` protocol as
the single-device `HKVTable`, so consumers and benchmarks drive local and
sharded tables through one code path.  Owner-side table traffic inside
the shard bodies goes through `HKVTable.wrap(...)` — this module never
touches the op engine directly.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import ops as ops_mod
from repro.core import u64
from repro.core.api import HKVTable, dedupe_keys, normalize_keys
from repro.core.merge import EvictionStream
from repro.core.ops import ExportResult
from repro.core.tiered import TieredHKVTable, TieredState
from repro.core.u64 import U64
from repro.embedding.dynamic import HKVEmbedding


def _smap(body, *, mesh, in_specs, out_specs):
    """`jax.shard_map` compiled as one program.  Called eagerly, a
    shard_map body runs primitive by primitive, compiling each one afresh
    on every call; under an enclosing jit this inner jit is inlined."""
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _obs_tel():
    """Deferred observer import (the telemetry branch only — same
    discipline as `repro.core.ops._obs`)."""
    from repro.obs import telemetry as obs_telemetry

    return obs_telemetry


@dataclasses.dataclass(frozen=True)
class ShardedHKVEmbedding:
    """HKVEmbedding sharded over mesh axes (default: every mesh axis)."""

    emb: HKVEmbedding              # GLOBAL capacity; local = capacity / n_shards
    axis_names: tuple              # mesh axes the table shards over
    capacity_factor: float = 2.0

    def __post_init__(self):
        e = self.emb
        if e.value_tier == "hmem" or (e.is_tiered
                                      and e.cold_value_tier == "hmem"):
            raise NotImplementedError(
                "a sharded table keeps every value plane in device memory: "
                "a shard_map body cannot place an array in host memory, so "
                "value_tier='hmem' / cold_value_tier='hmem' is not supported "
                "here (pass 'hbm')")

    def local_embedding(self, n_shards: int) -> HKVEmbedding:
        def shard_cap(c):
            return max(128, (c // n_shards // 128) * 128)

        return dataclasses.replace(
            self.emb, capacity=shard_cap(self.emb.capacity),
            hot_capacity=(shard_cap(self.emb.hot_capacity)
                          if self.emb.is_tiered else None),
        )

    # -- routing helpers (shard-local code, used under shard_map) -----------

    def _owner(self, keys: U64, n_shards: int) -> jax.Array:
        _, h2 = u64.hash_pair(keys)
        own = (u64.fmix32(h2 ^ jnp.uint32(0x2545F491)) % jnp.uint32(n_shards)).astype(
            jnp.int32
        )
        return jnp.where(u64.is_empty(keys), n_shards, own)

    def _route(self, keys: U64, n_shards: int, cap: int):
        """Sort unique keys by owner; build [n_shards, cap] send buffers.

        Returns (send_hi, send_lo, slot_of_key [N] (-1 = overflow), order info)
        """
        n = keys.hi.shape[0]
        owner = self._owner(keys, n_shards)
        order = jnp.argsort(owner)
        o_s = owner[order]
        iota = jnp.arange(n, dtype=jnp.int32)
        is_new = jnp.concatenate([jnp.ones((1,), bool), o_s[1:] != o_s[:-1]])
        rank = iota - jax.lax.cummax(jnp.where(is_new, iota, -1))
        ok = (o_s < n_shards) & (rank < cap)
        slot = jnp.where(ok, o_s * cap + rank, n_shards * cap)
        send_hi = jnp.full((n_shards * cap,), u64.EMPTY_HI, jnp.uint32).at[slot].set(
            keys.hi[order], mode="drop"
        )
        send_lo = jnp.full((n_shards * cap,), u64.EMPTY_LO, jnp.uint32).at[slot].set(
            keys.lo[order], mode="drop"
        )
        # slot of each original key (for the return trip)
        key_slot = jnp.full((n,), -1, jnp.int32).at[order].set(
            jnp.where(ok, slot, -1)
        )
        return send_hi.reshape(n_shards, cap), send_lo.reshape(n_shards, cap), key_slot

    # -- shard-local bodies ---------------------------------------------------

    def _lookup_body(self, n_shards, cap, train, state, khi, klo,
                     promote=True, telemetry=None):
        """Executes per shard under shard_map: khi/klo are the LOCAL tokens'
        unique keys (padded with EMPTY).  Returns (state, rows, found, ovf).

        `promote=False` makes the read a PURE READER on tiered shards
        (no miss-path re-admission — the membership-query path).
        `telemetry` is a SHARD-LOCAL sink (the caller psums its total
        across the mesh — see `find_keys`)."""
        axis = self.axis_names
        local = self.local_embedding(n_shards)
        keys = U64(khi, klo)
        send_hi, send_lo, key_slot = self._route(keys, n_shards, cap)
        # dispatch keys to owners
        recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
        recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)
        rk = U64(recv_hi.reshape(-1), recv_lo.reshape(-1))
        init = local.default_rows(rk)
        # owner-side table op through the handle — flat or tiered, the
        # embedding's wrap() picks; the inserter backend follows the
        # embedding config ('auto' -> fused Pallas on TPU)
        t = local.wrap(state)
        if train:
            res = t.find_or_insert(rk, init, telemetry=telemetry)
            state, rows = res.table.state, res.values
            present = res.found  # pre-existing (find_or_insert contract)
        else:
            # handle readers carry the backend: shard-local finds run the
            # fused find_scan pass when the embedding config picked kernel
            if isinstance(t, TieredHKVTable):
                fr = t.find(rk, promote=promote, telemetry=telemetry)
            else:
                fr = t.find(rk, telemetry=telemetry)
            rows = jnp.where(fr.found[:, None], fr.values, init[:, : local.dim])
            present = fr.found
            succ = getattr(fr, "table", None)  # tiered find promotes:
            if succ is not None:               # thread the successor state
                state = succ.state
        # return rows to requesters with the presence flag as one extra
        # column (exact in float: the flag is 0.0 or 1.0)
        rows = jnp.concatenate(
            [rows, present.astype(rows.dtype)[:, None]], axis=1
        ).reshape(n_shards, cap, local.dim + 1)
        back = jax.lax.all_to_all(rows, axis, 0, 0, tiled=True)
        back = back.reshape(n_shards * cap, local.dim + 1)
        ovf = jnp.sum((key_slot < 0) & ~u64.is_empty(keys))
        # overflowed / padded keys fall back to deterministic init rows
        fallback = local.default_rows(keys)
        routed = key_slot >= 0
        out = jnp.where(
            routed[:, None],
            back[jnp.clip(key_slot, 0), : local.dim],
            fallback,
        )
        found = routed & (back[jnp.clip(key_slot, 0), local.dim] > 0)
        return state, out, found, ovf

    def _grad_body(self, n_shards, cap, state, khi, klo, grads):
        axis = self.axis_names
        local = self.local_embedding(n_shards)
        keys = U64(khi, klo)
        send_hi, send_lo, key_slot = self._route(keys, n_shards, cap)
        gbuf = jnp.zeros((n_shards * cap, local.dim), grads.dtype).at[
            jnp.where(key_slot >= 0, key_slot, n_shards * cap)
        ].add(grads, mode="drop")
        recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
        recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)
        recv_g = jax.lax.all_to_all(gbuf.reshape(n_shards, cap, -1), axis, 0, 0,
                                    tiled=True).reshape(n_shards * cap, -1)
        rk = U64(recv_hi.reshape(-1), recv_lo.reshape(-1))
        # owner-side dedupe across sources: same key from several data shards.
        # Compacted form (group g's key at slot g) so the segment sums align
        # with the uniques directly — no batch-sized g_sum[d.gid] re-broadcast
        n = rk.hi.shape[0]
        d = dedupe_keys(rk)
        uniq = U64(
            jnp.full((n,), u64.EMPTY_HI, jnp.uint32)
            .at[d.gid].set(rk.hi[d.idx_sorted]),
            jnp.full((n,), u64.EMPTY_LO, jnp.uint32)
            .at[d.gid].set(rk.lo[d.idx_sorted]),
        )
        g_sum = jax.ops.segment_sum(recv_g[d.idx_sorted], d.gid,
                                    num_segments=n, indices_are_sorted=True)
        # structured gradient step: ONE table op, and on the kernel backend
        # ONE fused update_scan launch per shard body
        t = local.wrap(state)
        s = t.session()
        s.update_rows(uniq, ops_mod.RowUpdate(local.optimizer, g_sum))
        return s.commit().state

    def _upsert_body(self, n_shards, cap, state, khi, klo, values,
                     telemetry=None):
        """insert_or_assign with caller values routed to owners; statuses
        routed back (the ShardedHKVTable protocol path)."""
        axis = self.axis_names
        local = self.local_embedding(n_shards)
        keys = U64(khi, klo)
        n = khi.shape[0]
        d = dedupe_keys(keys)
        send_hi, send_lo, key_slot = self._route(d.unique, n_shards, cap)
        # last-writer-wins within the batch: route the group's final row
        v_u = values[d.last_index]
        vbuf = jnp.zeros((n_shards * cap, values.shape[1]), values.dtype).at[
            jnp.where(key_slot >= 0, key_slot, n_shards * cap)
        ].set(v_u, mode="drop")
        recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
        recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)
        recv_v = jax.lax.all_to_all(vbuf.reshape(n_shards, cap, -1), axis, 0, 0,
                                    tiled=True).reshape(n_shards * cap, -1)
        rk = U64(recv_hi.reshape(-1), recv_lo.reshape(-1))
        t = local.wrap(state)
        res = t.insert_or_assign(rk, recv_v, telemetry=telemetry)
        sbuf = res.status.astype(jnp.int32).reshape(n_shards, cap)
        back = jax.lax.all_to_all(sbuf, axis, 0, 0, tiled=True).reshape(-1)
        st_u = jnp.where(key_slot >= 0, back[jnp.clip(key_slot, 0)], 0)
        status = st_u[d.inverse].astype(jnp.int8)
        ovf = jnp.sum((key_slot < 0) & ~u64.is_empty(d.unique))
        return res.table.state, status, ovf

    def _assign_body(self, n_shards, cap, state, khi, klo, values):
        """Updater: route caller values to owners; owner-side assign (write
        existing keys only — misses are no-ops, the flat-table contract)."""
        axis = self.axis_names
        local = self.local_embedding(n_shards)
        d = dedupe_keys(U64(khi, klo))
        send_hi, send_lo, key_slot = self._route(d.unique, n_shards, cap)
        v_u = values[d.last_index]
        vbuf = jnp.zeros((n_shards * cap, values.shape[1]), values.dtype).at[
            jnp.where(key_slot >= 0, key_slot, n_shards * cap)
        ].set(v_u, mode="drop")
        recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
        recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)
        recv_v = jax.lax.all_to_all(vbuf.reshape(n_shards, cap, -1), axis, 0, 0,
                                    tiled=True).reshape(n_shards * cap, -1)
        rk = U64(recv_hi.reshape(-1), recv_lo.reshape(-1))
        return local.wrap(state).assign(rk, recv_v).state

    def _erase_body(self, n_shards, cap, state, khi, klo):
        """Structural: route keys to owners; owner-side erase."""
        axis = self.axis_names
        local = self.local_embedding(n_shards)
        send_hi, send_lo, _slot = self._route(U64(khi, klo), n_shards, cap)
        recv_hi = jax.lax.all_to_all(send_hi, axis, 0, 0, tiled=True)
        recv_lo = jax.lax.all_to_all(send_lo, axis, 0, 0, tiled=True)
        rk = U64(recv_hi.reshape(-1), recv_lo.reshape(-1))
        return local.wrap(state).erase(rk).state

    # -- public API (call under `with mesh:` inside jit) ---------------------

    def create_sharded(self, mesh):
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        local = self.local_embedding(n_shards)

        def body():
            return local.create().state

        specs = self.state_specs()
        return _smap(body, mesh=mesh, in_specs=(), out_specs=specs)()

    def state_specs(self):
        ax = self.axis_names
        # Derived from the state's own tree (works for flat HKVState AND
        # the tiered two-state pytree): array leaves shard their leading
        # (bucket/row) axis; scalar clocks/epoch are advanced in LOCKSTEP
        # (every shard executes the same op sequence) — replicated under
        # shard_map, not sharded.
        shape = jax.eval_shape(lambda: self.local_embedding(1).create().state)
        return jax.tree.map(
            lambda a: P(ax, *([None] * (a.ndim - 1))) if a.ndim >= 1 else P(),
            shape,
        )

    def _uniq(self, tokens):
        """Local dedupe: unique keys (EMPTY-padded) + inverse map."""
        d = dedupe_keys(self.emb.keys_of(tokens))
        return d.unique, d.inverse

    def _dp_axes(self, mesh):
        return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

    def lookup(self, mesh, state, tokens, *, train: bool):
        """tokens: [B, S] (data-sharded). Returns (state, rows, overflow)."""
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        flat = tokens.reshape(-1)
        per_shard = max(flat.shape[0] // max(np.prod([mesh.shape[a] for a in dp]), 1), 1)
        cap = self._cap(per_shard, n_shards)

        def body(state, toks):
            uk, inv = self._uniq(toks.reshape(-1))
            state, rows, _found, ovf = self._lookup_body(
                n_shards, cap, train, state, uk.hi, uk.lo
            )
            return state, rows[inv], ovf.reshape(1)  # rank-1 for out_specs

        specs = self.state_specs()
        out = _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp, None)),
            out_specs=(specs, P(dp, None), P(dp)),
        )(state, tokens.reshape(tokens.shape[0], -1))
        state, rows, ovf = out
        return state, rows.reshape(tokens.shape + (self.emb.dim,)), jnp.sum(ovf)

    def find_keys(self, mesh, state, keys: U64, *, train: bool = False,
                  promote: bool = True, telemetry=None):
        """Key-level lookup: keys U64 [N] (N divisible by the dp world size).

        Returns (state, values [N, dim], found [N], overflow).  Misses
        return ZERO rows (the table-surface contract, unlike the embedding
        path's deterministic init fallback).

        `telemetry=` records ONE whole-mesh `OpTelemetry` into the sink
        (shard-local sinks inside the body, leaves psum-summed over every
        mesh axis, so the record is replicated and exact — DESIGN.md
        §Observability).  None is the exact pre-telemetry path."""
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        per_shard = max(keys.hi.shape[0] // max(np.prod([mesh.shape[a] for a in dp]), 1), 1)
        cap = self._cap(per_shard, n_shards)
        with_tel = telemetry is not None
        all_axes = tuple(mesh.axis_names)

        def body(state, khi, klo):
            sink = None
            if with_tel:
                obs_telemetry = _obs_tel()
                sink = obs_telemetry.TelemetrySink()
            d = dedupe_keys(U64(khi, klo))
            state, rows, found, ovf = self._lookup_body(
                n_shards, cap, train, state, d.unique.hi, d.unique.lo,
                promote=promote, telemetry=sink,
            )
            rows_o = rows[d.inverse]
            found_o = found[d.inverse] & ~u64.is_empty(U64(khi, klo))
            if not train:  # reader contract: zeros where not found
                rows_o = jnp.where(found_o[:, None], rows_o, 0.0)
            if with_tel:
                tel = obs_telemetry.psum_telemetry(sink.total(), all_axes)
                return state, rows_o, found_o, ovf.reshape(1), tel
            return state, rows_o, found_o, ovf.reshape(1)

        specs = self.state_specs()
        out_specs = (specs, P(dp, None), P(dp), P(dp))
        if with_tel:
            out_specs = out_specs + (P(),)  # psum-replicated counters
        out = _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp), P(dp)),
            out_specs=out_specs,
        )(state, keys.hi, keys.lo)
        if with_tel:
            state, rows, found, ovf, tel = out
            telemetry.record(
                "sharded_find_or_insert" if train else "sharded_find", tel)
        else:
            state, rows, found, ovf = out
        return state, rows, found, jnp.sum(ovf)

    def upsert_keys(self, mesh, state, keys: U64, values, *, telemetry=None):
        """Key-level insert_or_assign: values routed to owner shards.

        Returns (state, status [N] int8, overflow).  `telemetry=` records
        one whole-mesh `OpTelemetry` (same psum pattern as `find_keys`)."""
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        per_shard = max(keys.hi.shape[0] // max(np.prod([mesh.shape[a] for a in dp]), 1), 1)
        cap = self._cap(per_shard, n_shards)
        with_tel = telemetry is not None
        all_axes = tuple(mesh.axis_names)

        def body(state, khi, klo, v):
            sink = None
            if with_tel:
                obs_telemetry = _obs_tel()
                sink = obs_telemetry.TelemetrySink()
            state, status, ovf = self._upsert_body(
                n_shards, cap, state, khi, klo, v, telemetry=sink,
            )
            if with_tel:
                tel = obs_telemetry.psum_telemetry(sink.total(), all_axes)
                return state, status, ovf.reshape(1), tel
            return state, status, ovf.reshape(1)

        specs = self.state_specs()
        out_specs = (specs, P(dp), P(dp))
        if with_tel:
            out_specs = out_specs + (P(),)
        out = _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp), P(dp), P(dp, None)),
            out_specs=out_specs,
        )(state, keys.hi, keys.lo, values)
        if with_tel:
            state, status, ovf, tel = out
            telemetry.record("sharded_insert_or_assign", tel)
        else:
            state, status, ovf = out
        return state, status, jnp.sum(ovf)

    def assign_keys(self, mesh, state, keys: U64, values):
        """Key-level updater: values routed to owner shards; misses no-op."""
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        per_shard = max(keys.hi.shape[0] // max(np.prod([mesh.shape[a] for a in dp]), 1), 1)
        cap = self._cap(per_shard, n_shards)

        def body(state, khi, klo, v):
            return self._assign_body(n_shards, cap, state, khi, klo, v)

        specs = self.state_specs()
        return _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp), P(dp), P(dp, None)),
            out_specs=specs,
        )(state, keys.hi, keys.lo, values)

    def erase_keys(self, mesh, state, keys: U64):
        """Key-level structural erase routed to owner shards."""
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        per_shard = max(keys.hi.shape[0] // max(np.prod([mesh.shape[a] for a in dp]), 1), 1)
        cap = self._cap(per_shard, n_shards)

        def body(state, khi, klo):
            return self._erase_body(n_shards, cap, state, khi, klo)

        specs = self.state_specs()
        return _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp), P(dp)),
            out_specs=specs,
        )(state, keys.hi, keys.lo)

    def apply_grads(self, mesh, state, tokens, grads):
        n_shards = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        dp = self._dp_axes(mesh)
        per_shard = max(
            tokens.size // max(np.prod([mesh.shape[a] for a in dp]), 1), 1
        )
        cap = self._cap(per_shard, n_shards)

        def body(state, toks, g):
            flat = toks.reshape(-1)
            g = g.reshape(-1, self.emb.dim)
            uk, inv = self._uniq(flat)
            n = flat.shape[0]
            # sum grads per unique (scatter to representative positions)
            g_uniq = jnp.zeros((n, self.emb.dim), g.dtype).at[inv].add(g)
            return self._grad_body(n_shards, cap, state, uk.hi, uk.lo, g_uniq)

        specs = self.state_specs()
        return _smap(
            body, mesh=mesh,
            in_specs=(specs, P(dp, None), P(dp, None, None)),
            out_specs=specs,
        )(state, tokens.reshape(tokens.shape[0], -1),
          grads.reshape(tokens.shape[0], -1, self.emb.dim))

    def _cap(self, per_shard_tokens: int, n_shards: int) -> int:
        c = int(per_shard_tokens * self.capacity_factor / n_shards)
        return max(8, -(-c // 8) * 8)


# =============================================================================
# ShardedHKVTable — the KVTable-protocol handle over the sharded engine
# =============================================================================


class ShardedFind(NamedTuple):
    values: jax.Array   # [N, dim] (zeros where not found)
    found: jax.Array    # bool [N]
    overflow: jax.Array  # int — keys that missed their routing budget
    # Successor handle: identical to the queried table for flat shards;
    # carries the promotion's effects when the shards are tiered (cold
    # hits re-admitted hot-side — DESIGN.md §2.5).  Callers that treat
    # find as a pure reader may ignore it.
    table: "ShardedHKVTable" = None


class ShardedUpsert(NamedTuple):
    table: "ShardedHKVTable"
    status: jax.Array   # int8 [N] merge status codes (0 where unrouted)
    overflow: jax.Array

    @property
    def ok(self) -> jax.Array:
        return (self.status >= 1) & (self.status <= 3)


class ShardedFindOrInsert(NamedTuple):
    table: "ShardedHKVTable"
    values: jax.Array
    found: jax.Array
    overflow: jax.Array


class ShardedSweep(NamedTuple):
    table: "ShardedHKVTable"
    swept: jax.Array     # int32 [] — entries removed across all shards


class ShardedEvictIf(NamedTuple):
    table: "ShardedHKVTable"
    # Per-shard coldest-first streams concatenated shard-major: lanes
    # [i*budget, (i+1)*budget) are shard i's rank order (2*budget per
    # shard when the shards are tiered).  The budget is PER SHARD —
    # sweeps are bucket-local, so per-shard application IS owner-routed.
    evicted: EvictionStream
    count: jax.Array     # int32 []


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ShardedHKVTable:
    """One table sharded over a mesh, behind the same handle discipline as
    `HKVTable`: sharded `state` is the only pytree leaf; the engine
    (`ShardedHKVEmbedding`) and mesh are static aux data.  Implements the
    `KVTable` protocol, so harness code is agnostic to whether a table
    lives on one device or a pod."""

    state: object                  # HKVState with leaves sharded over the mesh
    semb: ShardedHKVEmbedding
    mesh: object

    def tree_flatten(self):
        return (self.state,), (self.semb, self.mesh)

    @classmethod
    def tree_unflatten(cls, aux, children):
        semb, mesh = aux
        return cls(state=children[0], semb=semb, mesh=mesh)

    # -- construction ----------------------------------------------------------

    @classmethod
    def create(cls, mesh, emb: Optional[HKVEmbedding] = None, *,
               axis_names: Optional[tuple] = None,
               capacity_factor: float = 2.0, **emb_kwargs) -> "ShardedHKVTable":
        if emb is None:
            emb = HKVEmbedding(**emb_kwargs)
        semb = ShardedHKVEmbedding(
            emb=emb, axis_names=axis_names or tuple(mesh.axis_names),
            capacity_factor=capacity_factor,
        )
        return cls(state=semb.create_sharded(mesh), semb=semb, mesh=mesh)

    def with_state(self, state) -> "ShardedHKVTable":
        return dataclasses.replace(self, state=state)

    # -- static views ----------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.semb.axis_names]))

    @property
    def capacity(self) -> int:
        # realized capacity: per-shard rounding times shard count (both
        # tiers' slots when the local tables are tiered)
        local = self.semb.local_embedding(self.n_shards)
        return local.total_capacity * self.n_shards

    @property
    def dim(self) -> int:
        return self.semb.emb.dim

    # -- KVTable protocol ------------------------------------------------------

    def find(self, keys, *, promote: bool = True,
             telemetry=None) -> ShardedFind:
        """Lookup.  On tiered shards the default runs the miss-path
        promotion (keep `.table` to retain its effects); pass
        `promote=False` for the pure-reader form — serve-style callers
        that discard the successor handle should, or every lookup pays
        two structural upserts per shard that are then thrown away."""
        state, values, found, ovf = self.semb.find_keys(
            self.mesh, self.state, normalize_keys(keys), train=False,
            promote=promote, telemetry=telemetry,
        )
        return ShardedFind(values=values, found=found, overflow=ovf,
                           table=self.with_state(state))

    def insert_or_assign(self, keys, values, *,
                         telemetry=None) -> ShardedUpsert:
        state, status, ovf = self.semb.upsert_keys(
            self.mesh, self.state, normalize_keys(keys), values,
            telemetry=telemetry,
        )
        return ShardedUpsert(table=self.with_state(state), status=status,
                             overflow=ovf)

    def find_or_insert(self, keys, *, telemetry=None) -> ShardedFindOrInsert:
        """Admission-controlled lookup; misses insert the deterministic
        hash-derived init rows (routing caller init rows is not supported —
        owner shards recompute the init from the key)."""
        state, values, found, ovf = self.semb.find_keys(
            self.mesh, self.state, normalize_keys(keys), train=True,
            telemetry=telemetry,
        )
        return ShardedFindOrInsert(table=self.with_state(state), values=values,
                                   found=found, overflow=ovf)

    def assign(self, keys, values) -> "ShardedHKVTable":
        """Updater: write values of existing keys (misses no-op).  Keys
        beyond the per-destination routing budget are dropped (same
        overflow contract as every routed op; they surface in the next
        op's `overflow` metric rather than here)."""
        return self.with_state(self.semb.assign_keys(
            self.mesh, self.state, normalize_keys(keys), values))

    def erase(self, keys) -> "ShardedHKVTable":
        return self.with_state(self.semb.erase_keys(
            self.mesh, self.state, normalize_keys(keys)))

    def clear(self) -> "ShardedHKVTable":
        local = self.semb.local_embedding(self.n_shards)
        specs = self.semb.state_specs()

        def body(state):
            return local.wrap(state).clear().state

        return self.with_state(_smap(
            body, mesh=self.mesh, in_specs=(specs,), out_specs=specs,
        )(self.state))

    def contains(self, keys) -> jax.Array:
        # pure reader: no miss-path promotion on tiered shards (a
        # membership probe must not pay — or cause — structural motion)
        _state, _values, found, _ovf = self.semb.find_keys(
            self.mesh, self.state, normalize_keys(keys), train=False,
            promote=False,
        )
        return found

    # -- maintenance (sweeps are bucket-local: per-shard application IS
    # owner-routed — every key's owner shard sweeps its own buckets) ----------

    def erase_if(self, pred) -> ShardedSweep:
        local = self.semb.local_embedding(self.n_shards)
        specs = self.semb.state_specs()
        ax = self.semb.axis_names

        def body(state, p):
            r = local.wrap(state).erase_if(p)
            return r.table.state, r.swept.reshape(1)

        state, swept = _smap(
            body, mesh=self.mesh,
            in_specs=(specs, jax.tree.map(lambda _: P(), pred)),
            out_specs=(specs, P(ax)),
        )(self.state, pred)
        return ShardedSweep(table=self.with_state(state),
                            swept=jnp.sum(swept))

    def evict_if(self, pred, budget: int) -> ShardedEvictIf:
        local = self.semb.local_embedding(self.n_shards)
        specs = self.semb.state_specs()
        ax = self.semb.axis_names

        def body(state, p):
            r = local.wrap(state).evict_if(p, budget)
            return r.table.state, tuple(r.evicted), r.count.reshape(1)

        stream_specs = EvictionStream(
            key_hi=P(ax), key_lo=P(ax), values=P(ax, None),
            score_hi=P(ax), score_lo=P(ax), mask=P(ax))
        state, stream, count = _smap(
            body, mesh=self.mesh,
            in_specs=(specs, jax.tree.map(lambda _: P(), pred)),
            out_specs=(specs, tuple(stream_specs), P(ax)),
        )(self.state, pred)
        return ShardedEvictIf(table=self.with_state(state),
                              evicted=EvictionStream(*stream),
                              count=jnp.sum(count))

    def stats(self):
        """`TableStats` over the whole mesh.  Sharded state leaves are
        globally-addressable arrays and stats never hash keys, so the
        same jnp reductions run over all shards' buckets at once.  For
        tiered shards the hot/cold summaries combine with the inclusive
        duplicates deduped through `size()` (the shard_map probe)."""
        from repro.maintenance import stats as stats_mod  # deferred: layering

        st = self.state
        if isinstance(st, TieredState) or hasattr(st, "hot"):
            hot = stats_mod.stats_from_planes(
                st.hot.key_hi, st.hot.key_lo, st.hot.score_hi, st.hot.score_lo)
            cold = stats_mod.stats_from_planes(
                st.cold.key_hi, st.cold.key_lo, st.cold.score_hi,
                st.cold.score_lo)
            return stats_mod.combine_stats(hot, cold, size=self.size())
        return stats_mod.stats_from_planes(st.key_hi, st.key_lo,
                                           st.score_hi, st.score_lo)

    # -- export (the multi-host publish seam: per-shard drain, lanes
    # concatenated shard-major — ROADMAP item closed by PR 5) -----------------

    @property
    def num_buckets(self) -> int:
        """Export-space bucket count PER SHARD (the `export_batch`
        iteration bound): each call drains the same local bucket range on
        every shard and concatenates the lanes, so iterating
        [0, num_buckets) covers the whole mesh exactly once."""
        local = self.semb.local_embedding(self.n_shards)
        nb = local.config().num_buckets
        if local.is_tiered:
            nb += local.cold_config().num_buckets
        return nb

    def export_batch(self, bucket_start: int,
                     bucket_count: int) -> ExportResult:
        """Stream local buckets [start, start+count) of EVERY shard,
        concatenated shard-major (`bucket_count * S * n_shards` lanes with
        the liveness mask).  Owner routing partitions keys, so lanes are
        disjoint across shards; tiered shards apply their own inclusive-
        copy dedupe inside the shard body (`TieredHKVTable.export_batch`)."""
        local = self.semb.local_embedding(self.n_shards)
        specs = self.semb.state_specs()
        ax = self.semb.axis_names

        def body(state):
            return tuple(local.wrap(state).export_batch(
                bucket_start, bucket_count))

        out = _smap(
            body, mesh=self.mesh, in_specs=(specs,),
            out_specs=(P(ax), P(ax), P(ax, None), P(ax), P(ax), P(ax)),
        )(self.state)
        return ExportResult(*out)

    def size(self) -> jax.Array:
        specs = self.semb.state_specs()
        ax = self.semb.axis_names
        local = self.semb.local_embedding(self.n_shards)

        def body(state):
            # through the handle so tiered shards dedupe their inclusive
            # hot/cold copies exactly like a single-device tiered table
            return local.wrap(state).size().astype(jnp.int32).reshape(1)

        per_shard = _smap(
            body, mesh=self.mesh, in_specs=(specs,), out_specs=P(ax),
        )(self.state)
        return jnp.sum(per_shard)

    def load_factor(self) -> jax.Array:
        return self.size().astype(jnp.float32) / float(self.capacity)

    # -- embedding-layer delegates (the training path) -------------------------

    def lookup(self, tokens, *, train: bool):
        state, rows, ovf = self.semb.lookup(self.mesh, self.state, tokens,
                                            train=train)
        return self.with_state(state), rows, ovf

    def apply_grads(self, tokens, grads) -> "ShardedHKVTable":
        return self.with_state(
            self.semb.apply_grads(self.mesh, self.state, tokens, grads)
        )
