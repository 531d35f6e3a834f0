"""ONE parametrized contract suite for every `KVTable` implementation.

The `KVTable` protocol (repro.core.api) is implemented by five table
families; this file is the single place their shared semantics are
pinned, replacing the per-impl ad-hoc roundtrip tests that used to live
in test_api.py / test_baselines.py:

  hkv_jnp      `HKVTable`, pure-jnp inserter backend
  hkv_kernel   `HKVTable`, fused Pallas upsert path (interpret mode on CPU)
  dict_oa      `DictKVTable` over open addressing (WarpCore family)
  dict_p2c     `DictKVTable` over bucketed power-of-two-choices (BGHT)
  tiered       `TieredHKVTable` (hot HBM + cold hmem hierarchy)
  sharded      `ShardedHKVTable` on a 1-device mesh (slow: shard_map
               compiles per op on CPU)

Covered: find / contains / insert_or_assign / find_or_insert / assign /
erase / clear / size / export_batch, plus EMPTY-sentinel padding and the
key-form normalization contract.  Where the contract FAMILIES differ by
design — dictionary tables may fail inserts where HKV evicts; sharded
tables recompute init rows owner-side — the differences are encoded in
the per-impl capability table below, not skipped silently.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import HKVTable, KVTable, TieredHKVTable, U64
from repro.core import ops as core_ops
from repro.baselines import DictKVTable
from repro.embedding.sparse_opt import SparseOptimizer
from repro.launch.mesh import make_mesh

BATCH = 64     # one jit cache entry per op across every test
DIM = 4
EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)

IMPLS = [
    "hkv_jnp",
    "hkv_kernel",
    "dict_oa",
    "dict_p2c",
    "tiered",
    "sharded",   # 1-device mesh; fast BECAUSE ops go through the jitted
                 # wrappers below (eager shard_map would recompile per call)
]

CAPS = {
    # has_export: export_batch/num_buckets exposed (sharded: per-shard
    #             drain + concatenation — the PR-5 ROADMAP close)
    # caller_init: find_or_insert takes the caller's init rows
    # has_scores: score metadata exists, so score/epoch sweep predicates
    #             are meaningful (dictionary tables carry zero planes —
    #             key predicates only)
    # has_find_rows: full-row reads + session-fused read mixes (find /
    #             find_rows / contains over one shared locate) — the HKV
    #             handle surface the PR-6 fused find kernel serves
    # has_row_update: session-structured `ops.RowUpdate` gradient steps
    #             (the apply_grads surface; ONE fused update_scan launch
    #             on backend='kernel') — flat HKV tables only: tiered
    #             routes updates through the hot tier, dictionaries have
    #             no updater surface
    "hkv_jnp": dict(has_export=True, caller_init=True, has_scores=True,
                    has_find_rows=True, has_row_update=True),
    "hkv_kernel": dict(has_export=True, caller_init=True, has_scores=True,
                       has_find_rows=True, has_row_update=True),
    "dict_oa": dict(has_export=True, caller_init=True, has_scores=False,
                    has_find_rows=False, has_row_update=False),
    "dict_p2c": dict(has_export=True, caller_init=True, has_scores=False,
                     has_find_rows=False, has_row_update=False),
    "tiered": dict(has_export=True, caller_init=True, has_scores=True,
                   has_find_rows=False, has_row_update=False),
    "sharded": dict(has_export=True, caller_init=False, has_scores=True,
                    has_find_rows=False, has_row_update=False),
}

_MESH = None


def _mesh():
    global _MESH
    if _MESH is None:
        import jax

        _MESH = make_mesh((1,), ("d",))
    return _MESH


def make_table(impl: str):
    if impl == "hkv_jnp":
        return HKVTable.create(capacity=2 * 128, dim=DIM, backend="jnp")
    if impl == "hkv_kernel":
        return HKVTable.create(capacity=2 * 128, dim=DIM, backend="kernel")
    if impl == "dict_oa":
        return DictKVTable.open_addressing(capacity=256, dim=DIM)
    if impl == "dict_p2c":
        return DictKVTable.bucketed_p2c(capacity=256, dim=DIM)
    if impl == "tiered":
        return TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                                     dim=DIM)
    if impl == "sharded":
        from repro.distributed.table_sharding import ShardedHKVTable

        return ShardedHKVTable.create(_mesh(), capacity=4 * 128, dim=DIM)
    raise AssertionError(impl)


# -- batch helpers (constant shapes: BATCH lanes, EMPTY-padded) ---------------


def pad_keys(keys) -> np.ndarray:
    keys = np.asarray(keys, np.uint64)
    out = np.full(BATCH, EMPTY, np.uint64)
    out[: len(keys)] = keys
    return out


def rows_for(keys: np.ndarray) -> jnp.ndarray:
    """Deterministic per-key value rows (column j = key + j)."""
    base = np.where(keys == EMPTY, 0, keys.astype(np.float64))
    return jnp.asarray(
        base[:, None] + np.arange(DIM)[None, :], jnp.float32)


# -- jitted op wrappers -------------------------------------------------------
#
# Every op goes through ONE module-level jitted closure: handles are
# pytrees with static cfg/mesh aux, so each (impl, op) pair compiles once
# for the whole matrix.  This is what makes the sharded param tractable —
# eager shard_map would otherwise recompile per call.


@jax.jit
def _j_read_plain(t, kh, kl):
    r = t.find(U64(kh, kl))
    return r.values[:, :DIM], r.found


@jax.jit
def _j_read_pure(t, kh, kl):        # tiered/sharded: no miss-path promotion
    r = t.find(U64(kh, kl), promote=False)
    return r.values[:, :DIM], r.found


@jax.jit
def _j_contains(t, kh, kl):
    return t.contains(U64(kh, kl))


@jax.jit
def _j_find_rows(t, kh, kl):
    r = t.find_rows(U64(kh, kl))
    return r.rows[:, :DIM], r.found, r.score_hi, r.score_lo


@jax.jit
def _j_session_read(t, kh, kl):
    """find + contains + find_rows fused over ONE shared locate."""
    k = U64(kh, kl)
    s = t.session()
    f = s.find(k)
    c = s.contains(k)
    r = s.find_rows(k)
    s.commit()     # readers only: the committed table is unchanged
    return (f.get().values[:, :DIM], f.get().found,
            r.get().rows[:, :DIM], c.get())


@jax.jit
def _j_upsert(t, kh, kl, v):
    r = t.insert_or_assign(U64(kh, kl), v)
    return r.table, r.ok


@jax.jit
def _j_foi(t, kh, kl, init):
    r = t.find_or_insert(U64(kh, kl), init)
    return r.table, r.values[:, :DIM], r.found


@jax.jit
def _j_foi_ownerinit(t, kh, kl):    # sharded: owner-side init rows
    r = t.find_or_insert(U64(kh, kl))
    return r.table, r.values[:, :DIM], r.found


@jax.jit
def _j_assign(t, kh, kl, v):
    return t.assign(U64(kh, kl), v)


@jax.jit
def _j_erase(t, kh, kl):
    return t.erase(U64(kh, kl))


@jax.jit
def _j_clear(t):
    return t.clear()


@jax.jit
def _j_size(t):
    return t.size()


# lr=0.5 x integer grads: the sgd step is exact in float32, so the
# updater contract below asserts equality, not allclose
_OPT = SparseOptimizer("sgd", lr=0.5)


@jax.jit
def _j_row_update(t, kh, kl, g):
    """The apply_grads shape: pre-update find + structured RowUpdate +
    contains in ONE session (the find shares its locate with the update)."""
    k = U64(kh, kl)
    s = t.session()
    f = s.find(k)
    r = s.update_rows(k, core_ops.RowUpdate(_OPT, g))
    c = s.contains(k)
    t2 = s.commit()
    return t2, f.get().values[:, :DIM], r.get().found, c.get()


@jax.jit
def _j_row_update_solo(t, kh, kl, g):
    """Structured RowUpdate alone — the ONE-launch fused route."""
    s = t.session()
    r = s.update_rows(U64(kh, kl), core_ops.RowUpdate(_OPT, g))
    t2 = s.commit()
    return t2, r.get().found


SWEEP_BUDGET = 32    # static per jit entry; >= every test's match count


@jax.jit
def _j_erase_if(t, pred):
    r = t.erase_if(pred)
    return r.table, r.swept


@jax.jit
def _j_evict_if(t, pred):
    r = t.evict_if(pred, SWEEP_BUDGET)
    return r.table, r.evicted, r.count


@jax.jit
def _j_stats(t):
    return t.stats()


def _planes(keys):
    if isinstance(keys, U64):
        return keys.hi, keys.lo
    keys = np.asarray(keys, np.uint64)
    return (jnp.asarray((keys >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


def read(table, keys):
    """Pure-reader find: (values, found), never mutating the table."""
    kh, kl = _planes(keys)
    if isinstance(table, TieredHKVTable) or hasattr(table, "mesh"):
        vals, found = _j_read_pure(table, kh, kl)
    else:
        vals, found = _j_read_plain(table, kh, kl)
    return np.asarray(vals), np.asarray(found)


def contains(table, keys):
    return np.asarray(_j_contains(table, *_planes(keys)))


def upsert(table, keys, values):
    t, ok = _j_upsert(table, *_planes(keys), values)
    return t, np.asarray(ok)


def find_or_insert(table, keys, init):
    if CAPS_CURRENT["caller_init"]:
        t, vals, found = _j_foi(table, *_planes(keys), init)
    else:
        t, vals, found = _j_foi_ownerinit(table, *_planes(keys))
    return t, np.asarray(vals), np.asarray(found)


def assign(table, keys, values):
    return _j_assign(table, *_planes(keys), values)


def erase(table, keys):
    return _j_erase(table, *_planes(keys))


def clear(table):
    return _j_clear(table)


def size(table) -> int:
    return int(_j_size(table))


CAPS_CURRENT = None


@pytest.fixture(params=IMPLS)
def impl(request):
    global CAPS_CURRENT
    CAPS_CURRENT = CAPS[request.param]
    return request.param


@pytest.fixture
def table(impl):
    return make_table(impl)


KEYS = np.arange(1, 25, dtype=np.uint64) * np.uint64(7919)  # 24 distinct keys


class TestReaderContract:
    def test_empty_table_reads(self, table):
        k = pad_keys(KEYS)
        vals, found = read(table, k)
        assert not found.any()
        assert np.allclose(vals, 0.0)
        assert not contains(table, k).any()
        assert size(table) == 0
        assert table.capacity > 0

    def test_find_agrees_with_contains(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        _, found = read(t, k)
        assert np.array_equal(found, contains(t, k))


class TestInserterContract:
    def test_insert_find_roundtrip(self, table):
        k = pad_keys(KEYS)
        v = rows_for(k)
        t, ok = upsert(table, k, v)
        # low load: every impl places every key (deterministic fixed batch)
        assert ok[: len(KEYS)].all()
        assert not ok[len(KEYS):].any()       # EMPTY padding is never "ok"
        vals, found = read(t, k)
        assert found[: len(KEYS)].all()
        assert not found[len(KEYS):].any()
        assert np.allclose(vals[: len(KEYS)], np.asarray(v)[: len(KEYS)])
        assert size(t) == len(KEYS)

    def test_overwrite_updates_in_place(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        t, ok = upsert(t, k, rows_for(k) + 100.0)
        assert ok[: len(KEYS)].all()
        vals, _ = read(t, k)
        assert np.allclose(vals[: len(KEYS)],
                           np.asarray(rows_for(k))[: len(KEYS)] + 100.0)
        assert size(t) == len(KEYS)     # no duplicate placements

    def test_duplicate_lanes_last_writer_wins(self, table):
        key = np.uint64(4242)
        k = pad_keys([key, key, key])
        v = jnp.asarray(
            np.stack([np.full(DIM, 1.0), np.full(DIM, 2.0),
                      np.full(DIM, 3.0)]
                     + [np.zeros(DIM)] * (BATCH - 3)), jnp.float32)
        t, _ = upsert(table, k, v)
        vals, found = read(t, pad_keys([key]))
        assert found[0]
        assert np.allclose(vals[0], 3.0)
        assert size(t) == 1

    def test_find_or_insert_admits_then_hits(self, table):
        k = pad_keys(KEYS)
        init = rows_for(k) + 0.5
        t, vals1, found1 = find_or_insert(table, k, init)
        assert not found1[: len(KEYS)].any()   # nothing existed
        if CAPS_CURRENT["caller_init"]:
            assert np.allclose(vals1[: len(KEYS)],
                               np.asarray(init)[: len(KEYS)])
        t, vals2, found2 = find_or_insert(t, k, rows_for(k) - 9.0)
        assert found2[: len(KEYS)].all()       # second pass: all hits
        # hits return the STORED rows (the first call's admissions)
        assert np.allclose(vals2[: len(KEYS)], vals1[: len(KEYS)])
        assert size(t) == len(KEYS)


class TestFusedReadContract:
    """The PR-6 reader surface: find_rows and session-fused read mixes
    must agree lane-for-lane with plain find/contains.  Running the matrix
    over BOTH HKV backends conformance-tests the fused find kernel path
    against the jnp one on identical states."""

    def _mixed(self, table):
        """Residents + erased keys + never-inserted keys in one batch."""
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        t = erase(t, pad_keys(KEYS[:6]))
        q = pad_keys(np.concatenate([KEYS, np.array([999983], np.uint64)]))
        return t, q

    def test_find_rows_matches_find(self, table):
        if not CAPS_CURRENT["has_find_rows"]:
            pytest.skip("no full-row read surface on this impl")
        t, q = self._mixed(table)
        vals, found = read(t, q)
        rows, rfound, shi, slo = map(np.asarray, _j_find_rows(t, *_planes(q)))
        np.testing.assert_array_equal(rfound, found)
        np.testing.assert_array_equal(rows, vals)
        # scores mask exactly like values: live lanes carry the entry's
        # score, misses/erased/padding lanes read zero
        score = (shi.astype(np.uint64) << np.uint64(32)) | slo.astype(
            np.uint64)
        assert (score[found] > 0).all()
        assert (score[~found] == 0).all()

    def test_session_read_matches_unfused(self, table):
        if not CAPS_CURRENT["has_find_rows"]:
            pytest.skip("no session find_rows surface on this impl")
        t, q = self._mixed(table)
        vals, found = read(t, q)
        f_vals, f_found, rows, cont = map(
            np.asarray, _j_session_read(t, *_planes(q)))
        np.testing.assert_array_equal(f_found, found)
        np.testing.assert_array_equal(cont, found)
        np.testing.assert_array_equal(f_vals, vals)
        np.testing.assert_array_equal(rows, vals)


class TestUpdaterContract:
    def test_assign_writes_existing_only(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        half = len(KEYS) // 2
        wk = pad_keys(np.concatenate([KEYS[:half],
                                      np.array([999983], np.uint64)]))
        t2 = assign(t, wk, jnp.full((BATCH, DIM), -5.0, jnp.float32))
        vals, found = read(t2, k)
        assert np.allclose(vals[:half], -5.0)
        assert np.allclose(vals[half: len(KEYS)],
                           np.asarray(rows_for(k))[half: len(KEYS)])
        # the missing key was NOT created (assign is non-structural)
        _, f999 = read(t2, pad_keys([999983]))
        assert not f999[0]
        assert size(t2) == len(KEYS)

    def test_row_update_trains_residents_only(self, table):
        """The apply_grads-shaped structured gradient step: residents move
        by exactly -lr*g, misses/padding train nothing and are NOT
        admitted, and both the fused solo route and the mixed session
        (find sharing its locate with the update) agree."""
        if not CAPS_CURRENT["has_row_update"]:
            pytest.skip("no structured row-update surface on this impl")
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        before = np.asarray(rows_for(k))
        q = pad_keys(np.concatenate([KEYS[:8],
                                     np.array([999983], np.uint64)]))
        g = jnp.full((BATCH, DIM), 2.0, jnp.float32)
        t2, pre_vals, found, cont = _j_row_update(t, *_planes(q), g)
        assert found[:8].all() and not found[8:].any()
        # the session's find ran BEFORE the update and sees pre-step rows
        np.testing.assert_array_equal(pre_vals[:8], before[:8])
        # contains after the update: same residency (updater != inserter)
        np.testing.assert_array_equal(np.asarray(cont), found)
        vals, vfound = read(t2, k)
        np.testing.assert_array_equal(vals[:8], before[:8] - 1.0)  # .5*2
        np.testing.assert_array_equal(vals[8: len(KEYS)],
                                      before[8: len(KEYS)])
        _, f999 = read(t2, pad_keys([999983]))
        assert not f999[0]
        assert size(t2) == len(KEYS)
        # the solo structured route (fused ONE-launch path) lands the
        # identical state
        t3, found3 = _j_row_update_solo(t, *_planes(q), g)
        np.testing.assert_array_equal(np.asarray(found3), found)
        vals3, _ = read(t3, k)
        np.testing.assert_array_equal(vals3, vals)


class TestStructuralContract:
    def test_erase_removes_and_is_idempotent(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        half = len(KEYS) // 2
        gone = pad_keys(np.concatenate([KEYS[:half],
                                        np.array([999983], np.uint64)]))
        t2 = erase(t, gone)
        _, found = read(t2, k)
        assert not found[:half].any()
        assert found[half: len(KEYS)].all()
        assert size(t2) == len(KEYS) - half
        t3 = erase(t2, gone)                   # idempotent
        assert size(t3) == len(KEYS) - half
        # erased keys can be re-inserted and found again
        t4, ok = upsert(t3, pad_keys(KEYS[:half]), rows_for(pad_keys(KEYS[:half])))
        assert ok[:half].all()
        _, found4 = read(t4, k)
        assert found4[: len(KEYS)].all()

    def test_clear_empties_and_reuses(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        t2 = clear(t)
        assert size(t2) == 0
        _, found = read(t2, k)
        assert not found.any()
        t3, ok = upsert(t2, k, rows_for(k))
        assert ok[: len(KEYS)].all()
        assert size(t3) == len(KEYS)


class TestMaintenanceContract:
    """The PR-5 surface: predicated sweeps + TableStats on every impl."""

    def test_erase_if_key_range(self, table):
        from repro.core import SweepPredicate

        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        lo, hi = int(KEYS[4]), int(KEYS[12])
        t2, swept = _j_erase_if(t, SweepPredicate.key_in_range(lo, hi))
        inside = (KEYS >= lo) & (KEYS < hi)
        assert int(swept) == inside.sum()
        _, found = read(t2, k)
        np.testing.assert_array_equal(found[: len(KEYS)], ~inside)
        assert size(t2) == len(KEYS) - inside.sum()
        # swept slots are reusable
        t3, ok = upsert(t2, k, rows_for(k))
        assert ok[: len(KEYS)].all()

    def test_erase_if_score_threshold(self, table):
        if not CAPS_CURRENT["has_scores"]:
            pytest.skip("dictionary tables carry no score metadata")
        from repro.core import SweepPredicate

        a, b = pad_keys(KEYS[:12]), pad_keys(KEYS[12:])
        t, _ = upsert(table, a, rows_for(a))       # clock 1
        t, _ = upsert(t, b, rows_for(b))           # clock 2
        # LRU scores = insert clock; threshold 2 expires only round 1
        # (tiered: demoted copies carry TRANSLATED scores, same domain)
        t2, swept = _j_erase_if(t, SweepPredicate.score_below(2))
        assert int(swept) >= 12                    # >=: inclusive cold copies
        _, found = read(t2, pad_keys(KEYS))
        assert not found[:12].any()
        assert found[12: len(KEYS)].all()

    def test_evict_if_returns_the_removed_entries(self, table):
        from repro.core import SweepPredicate

        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        lo, hi = int(KEYS[0]), int(KEYS[8])
        want = {int(x) for x in KEYS[(KEYS >= lo) & (KEYS < hi)]}
        t2, stream, count = _j_evict_if(
            t, SweepPredicate.key_in_range(lo, hi))
        assert int(count) == len(want)
        mask = np.asarray(stream.mask)
        khi = np.asarray(stream.key_hi, np.uint64)
        klo = np.asarray(stream.key_lo, np.uint64)
        got = {int((khi[i] << np.uint64(32)) | klo[i])
               for i in np.nonzero(mask)[0]}
        assert got == want
        # the evicted lanes carry the stored rows (the demotion transport)
        vals = np.asarray(stream.values)
        for i in np.nonzero(mask)[0]:
            key = (khi[i] << np.uint64(32)) | klo[i]
            np.testing.assert_allclose(
                vals[i, :DIM], np.asarray(rows_for(np.array([key]))[0]))
        # and are gone from the table
        _, found = read(t2, k)
        np.testing.assert_array_equal(
            found[: len(KEYS)], ~((KEYS >= lo) & (KEYS < hi)))

    def test_stats_sanity(self, table):
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        s = _j_stats(t)
        assert int(s.size) == len(KEYS)
        lf = float(s.load_factor)
        assert 0.0 < lf <= 1.0
        hist = np.asarray(s.occupancy_hist)
        assert (hist >= 0).all()
        # weighted occupancy equals the live count
        assert (hist * np.arange(len(hist))).sum() >= len(KEYS)
        q = np.asarray(_j_stats(t).score_quantiles(), np.uint64)
        assert q.shape == (5,)
        assert (np.diff(q.astype(np.int64)) >= 0).all()

    def test_empty_table_stats(self, table):
        s = _j_stats(table)
        assert int(s.size) == 0
        assert float(s.load_factor) == 0.0


class TestExportContract:
    def test_export_batch_streams_the_live_set(self, table):
        if not CAPS_CURRENT["has_export"]:
            pytest.skip("no export surface (sharded checkpoint: ROADMAP)")
        k = pad_keys(KEYS)
        t, _ = upsert(table, k, rows_for(k))
        t = erase(t, pad_keys(KEYS[:4]))
        seen = {}
        for b in range(t.num_buckets):
            exp = t.export_batch(b, 1)
            mask = np.asarray(exp.mask)
            khi = np.asarray(exp.key_hi, np.uint64)
            klo = np.asarray(exp.key_lo, np.uint64)
            vals = np.asarray(exp.values)
            for i in np.nonzero(mask)[0]:
                key = int((khi[i] << np.uint64(32)) | klo[i])
                assert key not in seen, "duplicate key in export stream"
                seen[key] = vals[i, :DIM]
        assert sorted(seen) == sorted(int(x) for x in KEYS[4:])
        fv, _ = read(t, k)
        for j, key in enumerate(KEYS):
            if key in seen:
                assert np.allclose(seen[int(key)], fv[j])


# -- reusable one-shot roundtrip (composed handles import this; e.g. the
# sharded-over-tiered test in test_tiered.py) --------------------------------


def protocol_roundtrip(table):
    """The single code path the benchmarks use, over any KVTable."""
    assert isinstance(table, KVTable)
    keys = np.arange(1, 65, dtype=np.uint64)
    vals = jnp.broadcast_to(jnp.arange(64, dtype=jnp.float32)[:, None],
                            (64, table.dim)) + 1.0
    rep = table.insert_or_assign(keys, vals)
    assert bool(np.asarray(rep.ok).all())
    table = rep.table
    assert int(table.size()) == 64
    assert 0.0 < float(table.load_factor()) <= 1.0
    f = table.find(keys)
    assert bool(np.asarray(f.found).all())
    np.testing.assert_allclose(np.asarray(f.values), np.asarray(vals))
    miss = table.find(np.arange(1000, 1010, dtype=np.uint64))
    assert not bool(np.asarray(miss.found).any())
    np.testing.assert_array_equal(np.asarray(miss.values), 0.0)
    assert bool(np.asarray(table.contains(keys)).all())
    return table


class TestKeyNormalization:
    def test_key_forms_are_equivalent(self, table):
        from repro.core import normalize_keys

        ids = [3, 17, 255]
        t, _ = upsert(table, pad_keys(np.array(ids, np.uint64)),
                      rows_for(pad_keys(np.array(ids, np.uint64))))
        # the signed-int form resolves to the same keys, negatives to the
        # EMPTY padding sentinel — and every impl ignores those lanes
        as_list = list(map(int, ids)) + [-1] * (BATCH - len(ids))
        _, found = read(t, normalize_keys(np.array(as_list, np.int64)))
        assert found[: len(ids)].all()
        assert not found[len(ids):].any()     # negative = EMPTY padding

    def test_protocol_isinstance(self, table):
        assert isinstance(table, KVTable)
