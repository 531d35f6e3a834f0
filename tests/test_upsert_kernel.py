"""Kernel/core parity for the fused Pallas upsert path (interpret mode).

The acceptance bar is BIT-IDENTITY: for randomized batches — duplicates,
EMPTY-sentinel padding, full buckets at λ=1.0, dual-bucket configs, every
score policy — `upsert_kernel` must produce exactly the statuses, evicted
pairs, and post-state (keys, digests, scores, values) of the pure-jnp
`core.merge.upsert`.  Both share the batch-closure orchestration
(`DESIGN.md §4`), so these tests pin down the kernel stage semantics:
the fused probe (match + occupancy/min + dual-bucket selection), the
rank-r victim claim, and the gather/scatter value kernels.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import merge, ops, table, u64
from repro.core.oracle import OracleTable
from repro.kernels import ops as kops

EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)


def _random_batch(rng, n, key_space, dup_frac=0.25, pad_frac=0.05):
    keys = rng.integers(0, key_space, size=n).astype(np.uint64)
    ndup = int(n * dup_frac)
    if ndup:
        keys[rng.integers(0, n, size=ndup)] = rng.choice(keys, size=ndup)
    npad = int(n * pad_frac)
    if npad:
        keys[rng.integers(0, n, size=npad)] = EMPTY
    return keys


def _assert_states_equal(a, b, ctx=""):
    for f in ("key_hi", "key_lo", "digests", "score_hi", "score_lo", "values",
              "clock_hi", "clock_lo", "epoch"):
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        np.testing.assert_array_equal(av, bv, err_msg=f"{ctx}: state.{f}")


@pytest.mark.parametrize("dual", [False, True])
@pytest.mark.parametrize("policy", ["lru", "lfu"])
def test_upsert_kernel_bit_identical_over_full_table(dual, policy):
    """3x capacity through the table: warm-up inserts, λ=1.0 evictions."""
    rng = np.random.default_rng(17 * (1 + dual) + len(policy))
    cfg = table.HKVConfig(
        capacity=4 * 128, dim=8, buckets_per_key=2 if dual else 1,
        score_policy=policy,
    )
    sj = table.create(cfg)
    sk = table.create(cfg)
    for step in range(8):
        keys = _random_batch(rng, 192, 2**50)
        k = u64.from_uint64(keys)
        vals = jnp.asarray(rng.normal(size=(192, 8)), jnp.float32)
        rj = merge.upsert(sj, cfg, k, vals)
        rk = kops.upsert_kernel(sk, cfg, k, vals, interpret=True)
        sj, sk = rj.state, rk.state
        np.testing.assert_array_equal(
            np.asarray(rj.status), np.asarray(rk.status),
            err_msg=f"step {step} status",
        )
        _assert_states_equal(sj, sk, f"step {step}")
    assert float(sj.load_factor()) == 1.0  # the eviction regime was exercised


def test_insert_and_evict_kernel_returns_identical_evictions():
    rng = np.random.default_rng(3)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4)
    sj = table.create(cfg)
    sk = table.create(cfg)
    # fill past capacity so evictions actually occur
    for step in range(4):
        keys = _random_batch(rng, 160, 2**40)
        k = u64.from_uint64(keys)
        vals = jnp.asarray(rng.normal(size=(160, 4)), jnp.float32)
        rj = ops.insert_and_evict(sj, cfg, k, vals, backend="jnp")
        # the public kernel wrapper, exercised directly
        rk = kops.insert_and_evict_kernel(sk, cfg, k, vals, interpret=True)
        sj, sk = rj.state, rk.state
        np.testing.assert_array_equal(
            np.asarray(rj.status), np.asarray(rk.status),
            err_msg=f"step {step}: status",
        )
        for f in ("key_hi", "key_lo", "values", "score_hi", "score_lo", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rj.evicted, f)),
                np.asarray(getattr(rk.evicted, f)),
                err_msg=f"step {step}: evicted.{f}",
            )
        _assert_states_equal(sj, sk, f"step {step}")
    assert int(rj.evicted.count()) > 0


def test_find_or_insert_kernel_matches_core():
    rng = np.random.default_rng(5)
    for dual in (False, True):
        cfg = table.HKVConfig(
            capacity=4 * 128, dim=8, buckets_per_key=2 if dual else 1
        )
        sj = table.create(cfg)
        sk = table.create(cfg)
        for step in range(6):
            keys = _random_batch(rng, 128, 2**18)  # small space -> many hits
            k = u64.from_uint64(keys)
            init = jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)
            rj = ops.find_or_insert(sj, cfg, k, init, backend="jnp")
            rk = ops.find_or_insert(sk, cfg, k, init, backend="kernel")
            sj, sk = rj.state, rk.state
            np.testing.assert_array_equal(np.asarray(rj.found), np.asarray(rk.found))
            np.testing.assert_array_equal(np.asarray(rj.status), np.asarray(rk.status))
            np.testing.assert_array_equal(np.asarray(rj.values), np.asarray(rk.values))
            _assert_states_equal(sj, sk, f"dual={dual} step {step}")


class TestFindOrInsertSinglePass:
    """The perf fix: find_or_insert used to run three probe passes
    (pre-locate, the upsert's internal locate, post-locate).  The closure
    now publishes post-op locations (`MergeResult.loc`), so find_or_insert
    issues NO probe beyond the upsert's own — pinned here, with bit-parity
    against an explicit old-style three-pass reference."""

    def _old_style(self, state, cfg, k, init):
        """The pre-fix sequence, spelled out: pre-locate + upsert +
        post-locate + gather (the parity reference)."""
        from repro.core import find as find_mod

        pre = find_mod.locate(state, cfg, k)
        res = merge.upsert(state, cfg, k, init, write_hit_values=False)
        post = find_mod.locate(res.state, cfg, k)
        vals = find_mod.gather_values(res.state, post, cfg.dim, cfg)
        vals = jnp.where(post.found[:, None], vals, init[:, : cfg.dim])
        return res.state, vals, pre.found, res.status

    @pytest.mark.parametrize("policy", ["lru", "lfu"])
    @pytest.mark.parametrize("dual", [False, True])
    def test_parity_with_three_pass_reference(self, dual, policy):
        rng = np.random.default_rng(29 + dual)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4,
                              buckets_per_key=2 if dual else 1,
                              score_policy=policy)
        s_new = table.create(cfg)
        s_old = table.create(cfg)
        for step in range(6):  # drives past capacity: hits, evicts, rejects
            keys = _random_batch(rng, 160, 2**16)
            k = u64.from_uint64(keys)
            init = jnp.asarray(rng.normal(size=(160, 4)), jnp.float32)
            rn = ops.find_or_insert(s_new, cfg, k, init, backend="jnp")
            so, vo, fo, sto = self._old_style(s_old, cfg, k, init)
            s_new, s_old = rn.state, so
            np.testing.assert_array_equal(np.asarray(rn.found), np.asarray(fo))
            np.testing.assert_array_equal(np.asarray(rn.status), np.asarray(sto))
            np.testing.assert_array_equal(np.asarray(rn.values), np.asarray(vo))
            _assert_states_equal(s_new, s_old,
                                 f"dual={dual} {policy} step {step}")

    @pytest.mark.parametrize("backend", ["jnp", "kernel"])
    def test_hit_evicted_within_same_batch_reports_gone(self, backend):
        """The published post-op location must not be stale: under LFU a
        batch can HIT key A (count -> 2) and in the same launch admit a
        higher-count miss B that evicts A's slot.  find_or_insert must
        then return A's ephemeral init row (as the old re-probe did), and
        B's value must never leak into A's lane."""
        cfg = table.HKVConfig(capacity=128, dim=2, score_policy="lfu")
        state = table.create(cfg)
        a = np.array([1], np.uint64)
        others = np.arange(2, 129, dtype=np.uint64)    # fills the bucket
        state = ops.insert_or_assign(
            state, cfg, u64.from_uint64(a), jnp.full((1, 2), 50.0)).state
        for _ in range(3):                             # others: count 3
            state = ops.insert_or_assign(
                state, cfg, u64.from_uint64(others),
                jnp.zeros((127, 2))).state
        # batch: A (hit, count 1 -> 2) + B x3 (miss, init count 3 beats 2)
        batch = np.array([1, 999, 999, 999], np.uint64)
        init = jnp.asarray([[-1.0, -1.0], [7.0, 7.0], [7.0, 7.0], [7.0, 7.0]],
                           jnp.float32)
        res = ops.find_or_insert(state, cfg, u64.from_uint64(batch), init,
                                 backend=backend)
        status = np.asarray(res.status)
        assert status[0] == 1 and (status[1:] == 3).all()  # A updated, B evicts
        vals = np.asarray(res.values)
        np.testing.assert_array_equal(vals[0], [-1.0, -1.0])  # A: init, not B
        np.testing.assert_array_equal(vals[1], [7.0, 7.0])
        # A really is gone from the table
        gone = ops.contains(res.state, cfg, u64.from_uint64(a))
        assert not bool(np.asarray(gone)[0])

    def test_jnp_path_issues_exactly_one_locate(self, monkeypatch):
        from repro.core import find as find_mod

        calls = {"n": 0}
        real = find_mod.locate

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(find_mod, "locate", counting)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4)
        state = table.create(cfg)
        k = u64.from_uint64(np.arange(1, 65, dtype=np.uint64))
        ops.find_or_insert(state, cfg, k, jnp.zeros((64, 4)), backend="jnp")
        assert calls["n"] == 1  # the closure's own locate stage, nothing else

    @pytest.mark.parametrize("dual", [False, True])
    def test_kernel_path_probe_pass_budget(self, dual, monkeypatch):
        """At most the closure's own passes: single-bucket = 1 locate_kernel,
        dual = 2 fused upsert_probe passes (locate + target select).  The
        pre-fix wrapper added 2 more locate passes on top."""
        from repro.kernels import upsert_scan as _us

        counts = {"locate": 0, "probe": 0}
        real_lk, real_up = kops.locate_kernel, _us.upsert_probe

        def clk(*a, **kw):
            counts["locate"] += 1
            return real_lk(*a, **kw)

        def cup(*a, **kw):
            counts["probe"] += 1
            return real_up(*a, **kw)

        monkeypatch.setattr(kops, "locate_kernel", clk)
        monkeypatch.setattr(_us, "upsert_probe", cup)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4,
                              buckets_per_key=2 if dual else 1)
        state = table.create(cfg)
        k = u64.from_uint64(np.arange(1, 65, dtype=np.uint64))
        kops.find_or_insert_kernel(state, cfg, k, jnp.zeros((64, 4)),
                                   interpret=True)
        if dual:
            assert (counts["locate"], counts["probe"]) == (0, 2)
        else:
            assert (counts["locate"], counts["probe"]) == (1, 0)


def test_custom_scores_admission_parity():
    """Admission control (Table 9): a low-score burst must be rejected
    identically by both backends; a high-score burst displaces residents."""
    rng = np.random.default_rng(11)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, score_policy="custom")
    mk_sc = lambda v, n: u64.from_uint64(np.full(n, v, np.uint64))
    sj = table.create(cfg)
    sk = table.create(cfg)
    resident = rng.integers(0, 2**40, size=3 * cfg.capacity).astype(np.uint64)
    for i in range(0, len(resident), 256):
        kb = resident[i : i + 256]
        k = u64.from_uint64(kb)
        v = jnp.zeros((len(kb), 4), jnp.float32)
        sj = ops.insert_or_assign(sj, cfg, k, v, mk_sc(1000, len(kb)), backend="jnp").state
        sk = ops.insert_or_assign(sk, cfg, k, v, mk_sc(1000, len(kb)), backend="kernel").state
    _assert_states_equal(sj, sk, "resident fill")
    burst = u64.from_uint64(rng.integers(2**41, 2**42, size=128).astype(np.uint64))
    zeros = jnp.zeros((128, 4), jnp.float32)
    for score, expect_any_admit in ((1, False), (10**9, True)):
        rj = ops.insert_or_assign(sj, cfg, burst, zeros, mk_sc(score, 128), backend="jnp")
        rk = ops.insert_or_assign(sk, cfg, burst, zeros, mk_sc(score, 128), backend="kernel")
        np.testing.assert_array_equal(np.asarray(rj.status), np.asarray(rk.status))
        _assert_states_equal(rj.state, rk.state, f"burst score={score}")
        admitted = np.isin(np.asarray(rk.status), (2, 3)).any()
        assert bool(admitted) == expect_any_admit


def test_kernel_path_matches_sequential_oracle():
    """End-to-end sanity against the per-key sequential oracle (contents)."""
    rng = np.random.default_rng(23)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
    state = table.create(cfg)
    orc = OracleTable(cfg.capacity, 4, buckets_per_key=2)
    for _ in range(5):
        keys = rng.integers(0, 2**30, size=160).astype(np.uint64)
        vals = rng.normal(size=(160, 4)).astype(np.float32)
        res = ops.insert_or_assign(
            state, cfg, u64.from_uint64(keys), jnp.asarray(vals), backend="kernel"
        )
        state = res.state
        want = np.asarray(orc.insert_or_assign(keys, vals), np.int8)
        np.testing.assert_array_equal(np.asarray(res.status), want)
    exp = ops.export_batch(state, cfg, 0, cfg.num_buckets)
    mask = np.asarray(exp.mask)
    got_keys = set(
        ((np.asarray(exp.key_hi, np.uint64) << np.uint64(32))
         | np.asarray(exp.key_lo, np.uint64))[mask].tolist()
    )
    want_keys = {k for k, _ in orc.items()}
    assert got_keys == want_keys


def test_backend_auto_and_validation():
    cfg = table.HKVConfig(capacity=128, dim=2)
    state = table.create(cfg)
    k = u64.from_uint64(np.arange(4, dtype=np.uint64))
    v = jnp.zeros((4, 2), jnp.float32)
    r = ops.insert_or_assign(state, cfg, k, v, backend="auto")  # -> jnp off-TPU
    assert np.isin(np.asarray(r.status), (2, 3)).all()
    with pytest.raises(ValueError, match="backend"):
        ops.insert_or_assign(state, cfg, k, v, backend="cuda")


def test_victim_order_is_deterministic_on_empty_slots():
    """Empties claim ascending slot order — both backends, bit-identical
    digests plane included (the structural scatter writes the same slots)."""
    cfg = table.HKVConfig(capacity=128, dim=2)  # one bucket: forced collisions
    keys = u64.from_uint64(np.arange(1, 9, dtype=np.uint64))
    vals = jnp.ones((8, 2), jnp.float32)
    sj = merge.upsert(table.create(cfg), cfg, keys, vals).state
    sk = kops.upsert_kernel(table.create(cfg), cfg, keys, vals, interpret=True).state
    _assert_states_equal(sj, sk)
    occ = np.asarray(sj.occupied_mask())[0]
    assert occ[:8].all() and not occ[8:].any()  # lowest slots first
