"""hmem tier-crossing coverage under jit (§3.6 / DESIGN.md §2.5).

The 'hmem' value placement routes every value-plane touch through
`tier_gather`/`tier_scatter` — on TPU an explicit host<->device crossing,
on backends without an addressable host space a structural split.  Either
way the CONTRACT is: bit-identical results to the 'hbm' tier, under jit,
for every op that moves value rows.  Pinned here:

  * tier_gather/tier_scatter round-trip (set and add) under jit;
  * find_or_insert on a `value_tier='hmem'` table — states, statuses,
    values all bit-equal to the hbm twin;
  * export_batch streaming through `tier_gather` — bit-equal to hbm.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import HKVTable, U64, u64
from repro.core import table as table_mod


def _plane(rows: np.ndarray) -> jax.Array:
    """Pack logical rows [N, W] into a packed value plane."""
    n, w = rows.shape
    zero = jnp.zeros(table_mod.value_plane_shape(n, w), jnp.float32)
    return table_mod.tier_scatter("hbm", zero, jnp.arange(n, dtype=jnp.int32),
                                  jnp.asarray(rows, jnp.float32))


def _on(tier: str, plane: jax.Array) -> jax.Array:
    """The plane where `tier` keeps it: host memory for 'hmem'."""
    return table_mod.place_on_host(plane) if tier == "hmem" else plane


def _rows(tier: str, plane: jax.Array, n: int, w: int) -> np.ndarray:
    """Every logical row of a plane, read back through `tier` (an 'hbm'
    read of a host-tier plane first moves the plane to the device)."""
    if tier == "hbm":
        plane = jax.device_put(plane, jax.memory.Space.Device)
    return np.asarray(table_mod.tier_gather(
        tier, plane, jnp.arange(n, dtype=jnp.int32), w))


class TestGatherScatterRoundTrip:
    def test_jit_gather_matches_plain_indexing(self):
        rng = np.random.default_rng(0)
        logical = rng.normal(size=(256, 8)).astype(np.float32)
        values = _plane(logical)
        rows = jnp.asarray(rng.integers(0, 256, size=64), jnp.int32)
        for tier in ("hbm", "hmem"):
            got = jax.jit(
                lambda v, r: table_mod.tier_gather(tier, v, r, 8)
            )(_on(tier, values), rows)
            np.testing.assert_array_equal(np.asarray(got),
                                          logical[np.asarray(rows)])

    def test_jit_scatter_then_gather_round_trips(self):
        rng = np.random.default_rng(1)
        values = _plane(np.zeros((256, 4), np.float32))
        rows = jnp.asarray(rng.permutation(256)[:64], jnp.int32)  # unique
        updates = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)

        for tier in ("hbm", "hmem"):
            @jax.jit
            def rt(v, r, up):
                v2 = table_mod.tier_scatter(tier, v, r, up)
                return table_mod.tier_gather(tier, v2, r, 4), v2

            back, v2 = rt(_on(tier, values), rows, updates)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(updates))
            # untouched rows stay zero
            mask = np.ones(256, bool)
            mask[np.asarray(rows)] = False
            assert not _rows("hbm", v2, 256, 4)[mask].any()

    def test_jit_scatter_add_accumulates(self):
        values = _plane(np.ones((64, 2), np.float32))
        rows = jnp.asarray([3, 3, 7], jnp.int32)  # duplicate rows accumulate
        updates = jnp.full((3, 2), 2.0, jnp.float32)
        for tier in ("hbm", "hmem"):
            v2 = jax.jit(
                lambda v, r, up: table_mod.tier_scatter(tier, v, r, up, add=True)
            )(_on(tier, values), rows, updates)
            got = _rows("hbm", v2, 64, 2)
            np.testing.assert_allclose(got[3], 5.0)   # 1 + 2 + 2
            np.testing.assert_allclose(got[7], 3.0)
            np.testing.assert_allclose(got[1], 1.0)

    def test_oob_drop_mode_under_jit(self):
        """Rows past the last slot are dropped: the masked-lane contract
        every op relies on."""
        values = _plane(np.zeros((128, 2), np.float32))
        rows = jnp.asarray([2, 128], jnp.int32)   # 128 = one past the end
        updates = jnp.ones((2, 2), jnp.float32)
        for tier in ("hbm", "hmem"):
            v2 = jax.jit(
                lambda v, r, up: table_mod.tier_scatter(tier, v, r, up)
            )(_on(tier, values), rows, updates)
            got = _rows("hbm", v2, 128, 2)
            np.testing.assert_allclose(got[2], 1.0)
            assert got.sum() == 2.0               # OOB lane dropped
            assert float(np.asarray(v2).sum()) == 2.0  # nothing else in the plane

    def test_mask_rows_clears_only_dropped_rows(self):
        rng = np.random.default_rng(5)
        logical = rng.normal(size=(256, 5)).astype(np.float32)
        keep = rng.random(256) < 0.5
        for tier in ("hbm", "hmem"):
            v2 = jax.jit(
                lambda v, k: table_mod.tier_mask_rows(tier, v, k, 5)
            )(_on(tier, _plane(logical)), jnp.asarray(keep))
            np.testing.assert_array_equal(
                _rows("hbm", v2, 256, 5),
                np.where(keep[:, None], logical, 0.0))

    @pytest.mark.parametrize("op", ["gather", "scatter", "mask"])
    def test_hmem_plane_in_device_memory_is_refused(self, op):
        """An 'hmem' plane found in device memory raises: a host-tier
        table never silently runs as an HBM table."""
        values = _plane(np.zeros((128, 4), np.float32))
        rows = jnp.arange(8, dtype=jnp.int32)
        call = {
            "gather": lambda: table_mod.tier_gather("hmem", values, rows, 4),
            "scatter": lambda: table_mod.tier_scatter(
                "hmem", values, rows, jnp.ones((8, 4), jnp.float32)),
            "mask": lambda: table_mod.tier_mask_rows(
                "hmem", values, jnp.ones((128,), bool), 4),
        }[op]
        with pytest.raises(ValueError, match="pinned_host"):
            call()


class TestJitReturningTable:
    def test_successor_declared_where_the_table_lives(self, monkeypatch):
        """The successor's placement is the table's own, and one program
        serves every table with that placement."""
        monkeypatch.setattr(table_mod, "_declares_memory_kinds", lambda: True)
        traces = []

        def step(t, k):
            traces.append(1)
            r = t.insert_or_assign(k, jnp.ones((4, 3), jnp.float32))
            return r.table, r.status

        f = table_mod.jit_returning_table(step, donate_argnums=(0,))
        t = HKVTable.create(capacity=128, dim=3)
        want = [x.sharding for x in jax.tree.leaves(t)]
        for i in range(3):
            k = u64.from_uint64(np.arange(4 * i + 1, 4 * i + 5, dtype=np.uint64))
            t, status = f(t, k)
            assert status.shape == (4,)
        assert [x.sharding for x in jax.tree.leaves(t)] == want
        assert len(traces) == 1
        assert int(t.size()) == 12


def _twin_tables(dim=6, capacity=2 * 128):
    hbm = HKVTable.create(capacity=capacity, dim=dim, value_tier="hbm")
    hmem = HKVTable.create(capacity=capacity, dim=dim, value_tier="hmem")
    return hbm, hmem


class TestHmemOpParity:
    def test_find_or_insert_bit_identical_vs_hbm_under_jit(self):
        rng = np.random.default_rng(2)
        hbm, hmem = _twin_tables()

        @jax.jit
        def step(t, kh, kl, init):
            r = t.find_or_insert(U64(kh, kl), init)
            return r.table, r.values, r.found, r.status

        for _ in range(5):  # re-hits, inserts, evictions past capacity
            keys = rng.integers(0, 2**14, size=160).astype(np.uint64)
            k = u64.from_uint64(keys)
            init = jnp.asarray(rng.normal(size=(160, 6)), jnp.float32)
            hbm, v1, f1, s1 = step(hbm, k.hi, k.lo, init)
            hmem, v2, f2, s2 = step(hmem, k.hi, k.lo, init)
            np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
            np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
            np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
        for a, b in zip(jax.tree.leaves(hbm.state), jax.tree.leaves(hmem.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_export_batch_bit_identical_vs_hbm_under_jit(self):
        rng = np.random.default_rng(3)
        hbm, hmem = _twin_tables()
        keys = rng.integers(0, 2**40, size=200).astype(np.uint64)
        vals = jnp.asarray(rng.normal(size=(200, 6)), jnp.float32)
        ins = jax.jit(lambda t, kh, kl, v: t.insert_or_assign(U64(kh, kl), v).table)
        k = u64.from_uint64(keys)
        hbm, hmem = ins(hbm, k.hi, k.lo, vals), ins(hmem, k.hi, k.lo, vals)
        nb = hbm.cfg.num_buckets

        exp = jax.jit(lambda t: t.export_batch(0, nb))
        e1, e2 = exp(hbm), exp(hmem)
        for f in ("key_hi", "key_lo", "values", "score_hi", "score_lo", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(e1, f)), np.asarray(getattr(e2, f)),
                err_msg=f"export.{f}")

    def test_insert_and_evict_stream_bit_identical_vs_hbm(self):
        """The demotion transport itself must be tier-independent: a hot
        tier would otherwise demote different pairs depending on where its
        values live."""
        rng = np.random.default_rng(4)
        hbm, hmem = _twin_tables(dim=4, capacity=128)

        @jax.jit
        def step(t, kh, kl, v):
            r = t.insert_and_evict(U64(kh, kl), v)
            return r.table, r.status, r.evicted

        for _ in range(3):
            keys = rng.integers(0, 2**40, size=128).astype(np.uint64)
            k = u64.from_uint64(keys)
            vals = jnp.asarray(rng.normal(size=(128, 4)), jnp.float32)
            hbm, s1, ev1 = step(hbm, k.hi, k.lo, vals)
            hmem, s2, ev2 = step(hmem, k.hi, k.lo, vals)
            np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
            for f in ev1._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(ev1, f)), np.asarray(getattr(ev2, f)),
                    err_msg=f"evicted.{f}")
        assert int(ev1.count()) > 0
