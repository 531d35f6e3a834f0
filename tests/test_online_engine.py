"""OnlineEmbeddingEngine + publisher: miss-policy matrix, swap atomicity,
metrics sanity against the oracle, and the delta publication path."""

import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import HKVTable, TieredHKVTable
from repro.core.oracle import OracleTable
from repro.serving import (EmbeddingRequest, OnlineEmbeddingEngine,
                           OnlineTrainer, StaticSource, TablePublisher,
                           export_delta, ingest_delta)

DIM = 4
EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)


def _tiered_with_cold_resident(keys_cold):
    """A tiered table where `keys_cold` live ONLY in the cold tier (forced
    there by demotion from a tiny hot tier, then hot cleared via erase of
    a disjoint filler set is fragile — instead upsert into cold directly
    through the tier handles)."""
    t = TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                              dim=DIM)
    r = t.cold.insert_or_assign(
        keys_cold, jnp.ones((len(keys_cold), DIM)),
        custom_scores=np.arange(1, len(keys_cold) + 1, dtype=np.uint64))
    return t.with_tiers(t.hot, r.table)


class TestMissPolicyMatrix:
    KEYS = np.arange(1, 17, dtype=np.uint64)

    def _serve_once(self, table, policy, promote):
        eng = OnlineEmbeddingEngine(table, wave_size=32, miss_policy=policy,
                                    promote=promote)
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.run_until_drained()
        return eng, eng.completed[0]

    def test_readonly_pure_reader_leaves_table_untouched(self):
        t = _tiered_with_cold_resident(self.KEYS)
        eng, req = self._serve_once(t, "readonly", promote=False)
        assert req.found.all()                   # served from the cold tier
        src = eng.source.table
        assert src is t                          # no successor was installed
        assert not bool(np.asarray(t.hot.contains(self.KEYS)).any())

    def test_readonly_promote_reinstalls_cold_hits_hot(self):
        t = _tiered_with_cold_resident(self.KEYS)
        eng, req = self._serve_once(t, "readonly", promote=True)
        assert req.found.all()
        succ = eng.source.table
        assert succ is not t
        assert bool(np.asarray(succ.hot.contains(self.KEYS)).all())

    def test_readonly_misses_get_default_rows_and_stay_out(self):
        t = TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                                  dim=DIM)
        eng, req = self._serve_once(t, "readonly", promote=True)
        assert not req.found.any()
        assert np.allclose(req.values, 0.0)      # default vector fallback
        # reject policy: misses were NOT admitted
        eng2, req2 = self._serve_once(eng.source.table, "readonly",
                                      promote=True)
        assert not req2.found.any()

    def test_admit_installs_misses_for_the_next_wave(self):
        t = TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                                  dim=DIM)
        eng, req = self._serve_once(t, "admit", promote=False)
        assert not req.found.any()               # first sight: all misses
        eng2, req2 = self._serve_once(eng.source.table, "admit",
                                      promote=False)
        assert req2.found.all()                  # admitted by wave 1
        # stored rows are the admit-time init rows
        assert np.allclose(req2.values, req.values)

    def test_custom_default_row_feeds_miss_values_and_admission(self):
        t = HKVTable.create(capacity=2 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(
            t, wave_size=32, miss_policy="admit",
            default_row=lambda k: jnp.full((k.hi.shape[0], DIM), 2.5))
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.run_until_drained()
        assert np.allclose(eng.completed[0].values, 2.5)
        f = eng.source.table.find(self.KEYS)
        assert np.allclose(np.asarray(f.values), 2.5)


class TestWavePacking:
    def test_large_request_spans_waves_and_small_ones_pack(self):
        t = HKVTable.create(capacity=4 * 128, dim=DIM)
        keys = np.arange(1, 101, dtype=np.uint64)
        t = t.insert_or_assign(keys, jnp.asarray(
            np.tile(keys.astype(np.float32)[:, None], (1, DIM)))).table
        eng = OnlineEmbeddingEngine(t, wave_size=32, miss_policy="readonly")
        big = EmbeddingRequest(rid=0, keys=keys)          # 100 keys: 4 waves
        small = [EmbeddingRequest(rid=i + 1,
                                  keys=np.array([i + 1], np.uint64))
                 for i in range(3)]
        eng.submit(big)
        for r in small:
            eng.submit(r)
        done = eng.run_until_drained()
        assert {r.rid for r in done} == {0, 1, 2, 3}
        assert big.done and big.found.all()
        for j in range(DIM):
            assert np.array_equal(big.values[:, j],
                                  keys.astype(np.float32))
        m = eng.metrics()
        assert m.keys == 103 and m.hits == 103
        assert m.waves == 4                       # 100 + 3 packed into 4*32
        assert m.kv_per_s > 0 and m.p99_latency_s >= m.p50_latency_s


class TestPublisherAtomicity:
    def test_reader_never_observes_a_half_published_table(self):
        """Stamped tables: version i's table holds value-stamp i in every
        row.  A racing reader must always see ONE stamp across its whole
        find — a torn publish would mix stamps."""
        keys = np.arange(1, 33, dtype=np.uint64)
        base = HKVTable.create(capacity=2 * 128, dim=DIM)
        base = base.insert_or_assign(
            keys, jnp.zeros((len(keys), DIM))).table
        stamped = [base]
        for i in range(1, 12):
            stamped.append(
                base.assign(keys, jnp.full((len(keys), DIM), float(i))))
        pub = TablePublisher(stamped[0])
        stop = threading.Event()
        torn = []

        def reader():
            while not stop.is_set():
                version, t = pub.snapshot()
                vals = np.asarray(t.find(keys).values)
                uniq = np.unique(vals)
                if len(uniq) != 1:
                    torn.append(("mixed-stamps", version, uniq))
                elif int(uniq[0]) != version:
                    torn.append(("stamp-version-mismatch", version, uniq))

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for th in threads:
            th.start()
        for i in range(1, 12):
            assert pub.publish(stamped[i]) == i
        stop.set()
        for th in threads:
            th.join()
        assert not torn, torn[:3]
        assert pub.version == 11

    def test_offer_is_beaten_by_a_concurrent_publish(self):
        t0 = HKVTable.create(capacity=2 * 128, dim=DIM)
        pub = TablePublisher(t0)
        v, t = pub.snapshot()
        t1 = pub.publish(
            t0.insert_or_assign(np.arange(4, dtype=np.uint64),
                                jnp.ones((4, DIM))).table)
        # the engine's offer from the stale snapshot must be rejected
        stale_succ = t0.insert_or_assign(
            np.arange(10, 14, dtype=np.uint64), jnp.ones((4, DIM))).table
        assert not pub.offer(v, stale_succ)
        assert pub.rejected_offers == 1
        assert bool(np.asarray(pub.table.contains(
            np.arange(4, dtype=np.uint64))).all())
        # a fresh-snapshot offer applies
        v2, t2 = pub.snapshot()
        assert pub.offer(v2, stale_succ)
        assert pub.version == v2 + 1

    def test_engine_waves_never_mix_versions(self):
        """Each wave records the version it served from; the stamp of the
        rows it returned must match that version exactly."""
        keys = np.arange(1, 17, dtype=np.uint64)
        base = HKVTable.create(capacity=2 * 128, dim=DIM)
        base = base.insert_or_assign(keys, jnp.zeros((len(keys), DIM))).table
        pub = TablePublisher(base)
        eng = OnlineEmbeddingEngine(pub, wave_size=16,
                                    miss_policy="readonly")
        for i in range(5):
            eng.submit(EmbeddingRequest(rid=i, keys=keys.copy()))
            eng.step()
            pub.publish(base.assign(keys,
                                    jnp.full((len(keys), DIM), float(i + 1))))
        for i, (req, rep) in enumerate(zip(eng.completed, eng.reports)):
            stamps = np.unique(req.values)
            assert len(stamps) == 1
            assert int(stamps[0]) == rep.table_version == i


class TestMetricsMatchOracle:
    def test_hit_rate_matches_oracle_replay(self):
        """Admit-policy waves over a flat table vs OracleTable replaying
        the same batches: per-wave hit counts must agree exactly."""
        rng = np.random.default_rng(5)
        cap, wave = 2 * 128, 32
        t = HKVTable.create(capacity=cap, dim=DIM, buckets_per_key=2)
        orc = OracleTable(cap, DIM, buckets_per_key=2, policy="lru")
        eng = OnlineEmbeddingEngine(t, wave_size=wave, miss_policy="admit")
        zeros = np.zeros((wave, DIM), np.float32)
        for i in range(12):
            keys = rng.integers(0, 3 * cap, size=wave).astype(np.uint64)
            eng.submit(EmbeddingRequest(rid=i, keys=keys))
            rep = eng.step()
            st, _ = orc.find_or_insert(keys, zeros)
            want_hits = int(np.sum(np.asarray(st) == 1))
            assert rep.hits == want_hits, f"wave {i}"
            assert rep.size == wave
        m = eng.metrics()
        assert m.waves == 12 and m.keys == 12 * wave
        assert m.hits == sum(r.hits for r in eng.reports)
        assert 0.0 < m.hit_rate < 1.0


class TestTrainerAndDelta:
    def test_trainer_session_updates_are_visible_to_the_engine(self):
        pub = TablePublisher(HKVTable.create(capacity=2 * 128, dim=DIM))
        tr = OnlineTrainer(publisher=pub, publish_every=1, lr=0.5)
        keys = np.arange(1, 9, dtype=np.uint64)
        for _ in range(3):
            tr.train_step(keys, jnp.ones((len(keys), DIM)))
        eng = OnlineEmbeddingEngine(pub, wave_size=16,
                                    miss_policy="readonly")
        eng.submit(EmbeddingRequest(rid=0, keys=keys))
        eng.run_until_drained()
        req = eng.completed[0]
        assert req.found.all()
        assert np.allclose(req.values, -1.5)      # 3 steps * lr .5 * grad 1

    @pytest.mark.parametrize("src", ["flat", "tiered"])
    def test_export_ingest_delta_roundtrip(self, src):
        keys = np.arange(1, 151, dtype=np.uint64)
        vals = jnp.asarray(np.tile(keys.astype(np.float32)[:, None],
                                   (1, DIM)))
        if src == "flat":
            t = HKVTable.create(capacity=2 * 128, dim=DIM).insert_or_assign(
                keys, vals).table
        else:
            t = TieredHKVTable.create(hot_capacity=128,
                                      cold_capacity=2 * 128,
                                      dim=DIM).insert_or_assign(
                keys, vals).table
        delta = export_delta(t, chunk_buckets=1)
        assert delta.count == 150
        dst = ingest_delta(HKVTable.create(capacity=4 * 128, dim=DIM), delta,
                           batch=64)
        f = dst.find(keys)
        assert bool(np.asarray(f.found).all())
        assert np.allclose(np.asarray(f.values), np.asarray(vals))

    def test_delta_carry_scores_into_custom_policy(self):
        keys = np.arange(1, 17, dtype=np.uint64)
        scores = keys * np.uint64(10)
        t = HKVTable.create(capacity=2 * 128, dim=DIM,
                            score_policy="custom")
        t = t.insert_or_assign(keys, jnp.ones((len(keys), DIM)),
                               custom_scores=scores).table
        delta = export_delta(t)
        assert np.array_equal(np.sort(delta.scores),
                              np.sort(scores.astype(np.uint64)))
        dst = ingest_delta(
            HKVTable.create(capacity=2 * 128, dim=DIM,
                            score_policy="custom"),
            delta, carry_scores=True)
        exp = export_delta(dst)
        assert np.array_equal(
            np.sort(exp.scores), np.sort(scores.astype(np.uint64)))
        # the documented tiered destination (custom-policy hot tier)
        tiered_dst = ingest_delta(
            TieredHKVTable.create(hot_capacity=2 * 128,
                                  cold_capacity=4 * 128, dim=DIM,
                                  score_policy="custom"),
            delta, carry_scores=True)
        texp = export_delta(tiered_dst)
        assert np.array_equal(
            np.sort(texp.scores), np.sort(scores.astype(np.uint64)))
        assert bool(np.asarray(
            tiered_dst.contains(keys)).all())


class TestStaticSource:
    def test_static_source_accepts_every_offer(self):
        t = HKVTable.create(capacity=2 * 128, dim=DIM)
        s = StaticSource(t)
        v, tt = s.snapshot()
        assert v == 0 and tt is t
        t2 = t.insert_or_assign(np.arange(4, dtype=np.uint64),
                                jnp.ones((4, DIM))).table
        assert s.offer(v, t2)
        assert s.table is t2

    def test_static_source_rejects_stale_offers(self):
        """StaticSource runs the SAME compare-and-swap as TablePublisher:
        an offer from a superseded snapshot must lose, and versions bump
        from the CURRENT snapshot (never replay the caller's number)."""
        t = HKVTable.create(capacity=2 * 128, dim=DIM)
        s = StaticSource(t)
        v0, _ = s.snapshot()
        keys = np.arange(1, 5, dtype=np.uint64)
        t1 = t.insert_or_assign(keys, jnp.ones((4, DIM))).table
        assert s.offer(v0, t1)                   # fresh: applies
        v1, _ = s.snapshot()
        assert v1 == v0 + 1
        stale = t.insert_or_assign(keys, jnp.full((4, DIM), 9.0)).table
        assert not s.offer(v0, stale)            # stale: rejected
        assert s.rejected_offers == 1
        assert s.table is t1                     # newer table survives
        assert s.snapshot()[0] == v1             # version not clobbered

    def test_engine_and_scheduler_offers_interleave_without_clobber(self):
        """Two offer paths race on one StaticSource: the engine's admit
        waves and the maintenance scheduler's between-wave steps.  Every
        applied offer must bump the version; nothing may silently reuse a
        version or resurrect an older table."""
        from repro.maintenance import MaintenancePolicy, MaintenanceScheduler

        t = TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                                  dim=DIM)
        sched = MaintenanceScheduler(MaintenancePolicy(
            every_waves=1, sweep_budget=64))
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="admit",
                                    scheduler=sched)
        rng = np.random.default_rng(3)
        for i in range(6):
            keys = rng.integers(1, 4 * 128, size=16).astype(np.uint64)
            eng.submit(EmbeddingRequest(rid=i, keys=keys))
            eng.step()
        src = eng.source
        # every wave admitted (offer) and every scheduler step offered:
        # each accepted offer is exactly one version bump
        assert src.snapshot()[0] == src.offered
        assert src.offered + src.rejected_offers == (
            len(eng.reports) + sched.totals.runs - sched.totals.skipped_offers
        ) - sched.totals.deferred + sched.totals.skipped_offers
        # the admitted keys are actually in the final table (no clobber)
        last = eng.completed[-1]
        assert bool(np.asarray(
            src.table.contains(last.keys)).all())


class TestAuxColumnContract:
    """Tables carrying in-row optimizer state (rowwise_adagrad-style
    aux columns) must never leak aux columns to serving clients — the
    admit path slices served rows to exactly `table.dim`."""

    KEYS = np.arange(1, 33, dtype=np.uint64)

    @pytest.mark.parametrize("kind", ["flat", "tiered"])
    def test_admit_serves_dim_wide_rows_on_aux_tables(self, kind):
        if kind == "flat":
            t = HKVTable.create(capacity=2 * 128, dim=DIM, aux_value_dim=1)
        else:
            t = TieredHKVTable.create(hot_capacity=128,
                                      cold_capacity=2 * 128, dim=DIM,
                                      aux_value_dim=1)
        assert t.dim == DIM                       # dim excludes aux
        eng = OnlineEmbeddingEngine(t, wave_size=32, miss_policy="admit")
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.run_until_drained()
        req = eng.completed[0]
        assert req.values.shape == (len(self.KEYS), DIM)   # aux never leaks
        # admitted: the re-serve hits and is still exactly dim wide
        eng2 = OnlineEmbeddingEngine(eng.source.table, wave_size=32,
                                     miss_policy="admit")
        eng2.submit(EmbeddingRequest(rid=1, keys=self.KEYS.copy()))
        eng2.run_until_drained()
        req2 = eng2.completed[0]
        assert req2.found.all()
        assert req2.values.shape == (len(self.KEYS), DIM)
        # server-side rows still carry the aux column
        total = getattr(eng2.source.table, "hot", eng2.source.table)
        assert total.cfg.total_value_dim == DIM + 1

    def test_readonly_on_aux_table_is_dim_wide_too(self):
        t = HKVTable.create(capacity=2 * 128, dim=DIM, aux_value_dim=1)
        t = t.find_or_insert(self.KEYS,
                             jnp.ones((len(self.KEYS), DIM))).table
        eng = OnlineEmbeddingEngine(t, wave_size=32, miss_policy="readonly")
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.run_until_drained()
        req = eng.completed[0]
        assert req.found.all()
        assert req.values.shape == (len(self.KEYS), DIM)
        assert np.allclose(req.values, 1.0)


class TestWaveFnRebuild:
    """The cached wave closure is keyed on the published table's static
    signature: a mid-stream publish of a structurally different successor
    (flat→tiered, dim change) must rebuild the closure — stale baked-in
    flags would drop promotion or serve the wrong width."""

    KEYS = np.arange(1, 17, dtype=np.uint64)

    def test_flat_to_tiered_publish_rebuilds_and_promotes(self):
        flat = HKVTable.create(capacity=2 * 128, dim=DIM).insert_or_assign(
            self.KEYS, jnp.ones((len(self.KEYS), DIM))).table
        pub = TablePublisher(flat)
        eng = OnlineEmbeddingEngine(pub, wave_size=16,
                                    miss_policy="readonly", promote=True)
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.step()
        assert eng.completed[0].found.all()
        # flat + promote is a pure read: no successor was offered
        assert pub.offered == 0
        # mid-stream: the trainer retiers — keys now live ONLY cold
        pub.publish(_tiered_with_cold_resident(self.KEYS))
        eng.submit(EmbeddingRequest(rid=1, keys=self.KEYS.copy()))
        eng.step()
        req = eng.completed[1]
        assert req.found.all()
        assert np.allclose(req.values, 1.0)
        # the REBUILT closure promotes: cold hits were re-admitted hot and
        # the successor handle was offered back
        assert pub.offered == 1
        assert bool(np.asarray(pub.table.hot.contains(self.KEYS)).all())

    def test_dim_change_publish_serves_new_width(self):
        pub = TablePublisher(
            HKVTable.create(capacity=2 * 128, dim=DIM).insert_or_assign(
                self.KEYS, jnp.ones((len(self.KEYS), DIM))).table)
        eng = OnlineEmbeddingEngine(pub, wave_size=16,
                                    miss_policy="readonly")
        eng.submit(EmbeddingRequest(rid=0, keys=self.KEYS.copy()))
        eng.step()
        assert eng.completed[0].values.shape[1] == DIM
        wide = 2 * DIM
        pub.publish(
            HKVTable.create(capacity=2 * 128, dim=wide).insert_or_assign(
                self.KEYS, jnp.full((len(self.KEYS), wide), 3.0)).table)
        eng.submit(EmbeddingRequest(rid=1, keys=self.KEYS.copy()))
        eng.step()
        req = eng.completed[1]
        assert req.values.shape[1] == wide       # not the stale width
        assert np.allclose(req.values, 3.0)

    def test_scheduler_step_fn_rebuilds_on_signature_change(self):
        from repro.maintenance import MaintenancePolicy, MaintenanceScheduler

        sched = MaintenanceScheduler(MaintenancePolicy(
            every_waves=1, sweep_budget=64))
        flat = HKVTable.create(capacity=2 * 128, dim=DIM)
        sched.run(flat)
        sig_flat = sched._step_sig
        tiered = TieredHKVTable.create(hot_capacity=128,
                                       cold_capacity=2 * 128, dim=DIM)
        t2, rep = sched.run(tiered)              # must not reuse the flat fn
        assert sched._step_sig != sig_flat
        assert isinstance(t2, TieredHKVTable)
        assert sched.totals.runs == 2


class TestRequestShapes:
    """Requests larger than a wave and zero-length requests, through both
    miss policies AND both admission modes, checked lane-exactly against
    a one-shot find oracle on the same table."""

    @pytest.mark.parametrize("policy", ["readonly", "admit"])
    @pytest.mark.parametrize("admission", ["wave", "continuous"])
    def test_spanning_and_empty_requests_match_oracle(self, policy,
                                                      admission):
        cap, wave = 4 * 128, 32
        # DISTINCT keys: found/values then match the one-shot oracle even
        # across wave boundaries (duplicates would hit after an earlier
        # wave's admission)
        keys = np.arange(1, 101, dtype=np.uint64)        # 100 keys: 4 waves
        present = keys[::2]                              # half pre-resident
        vals = jnp.asarray(np.tile(
            present.astype(np.float32)[:, None], (1, DIM)))
        t = HKVTable.create(capacity=cap, dim=DIM).insert_or_assign(
            present, vals).table
        oracle = t.find(keys)                            # ONE-shot, pre-serve
        want_found = np.asarray(oracle.found)
        want_vals = np.where(want_found[:, None],
                             np.asarray(oracle.values), 0.0)
        eng = OnlineEmbeddingEngine(t, wave_size=wave, miss_policy=policy,
                                    admission=admission)
        big = EmbeddingRequest(rid=0, keys=keys)
        empty = EmbeddingRequest(rid=1, keys=np.zeros(0, np.uint64))
        eng.submit(big)
        eng.submit(empty)
        done = eng.run_until_drained()
        assert {r.rid for r in done} == {0, 1}
        assert empty.done and empty.values.shape == (0, DIM)
        assert big.done
        assert np.array_equal(big.found, want_found)
        assert np.allclose(big.values, want_vals)
        assert eng.idle
        m = eng.metrics()
        assert m.keys == 100
        assert m.hits == int(want_found.sum())
        if policy == "admit":                    # misses were admitted
            f2 = eng.source.table.find(keys)
            assert bool(np.asarray(f2.found).all())

    @pytest.mark.parametrize("admission", ["wave", "continuous"])
    def test_zero_length_only_completes_without_a_launch(self, admission):
        t = HKVTable.create(capacity=2 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="readonly",
                                    admission=admission)
        req = EmbeddingRequest(rid=0, keys=np.zeros(0, np.uint64))
        eng.submit(req)
        eng.run_until_drained()
        assert req.done and req.values.shape == (0, DIM)
        assert req.found.shape == (0,)
        assert not eng.reports                   # no wave was launched
        assert eng.idle


class TestContinuousAdmission:
    """Continuous-batch admission: splice-on-submit, dispatch-on-fill,
    poll() reaping, pipeline collapse — and result equivalence with the
    wave-granular path on the same replay."""

    def test_results_and_hit_rate_match_wave_mode(self):
        rng = np.random.default_rng(9)
        cap, wave = 4 * 128, 32
        reqs = [rng.integers(1, 3 * cap, size=rng.integers(1, 80))
                .astype(np.uint64) for _ in range(12)]

        def drive(admission):
            eng = OnlineEmbeddingEngine(
                HKVTable.create(capacity=cap, dim=DIM, buckets_per_key=2),
                wave_size=wave, miss_policy="admit", admission=admission)
            for i, k in enumerate(reqs):
                eng.submit(EmbeddingRequest(rid=i, keys=k.copy()))
            eng.run_until_drained()
            return eng

        w, c = drive("wave"), drive("continuous")
        # identical FIFO packing => identical waves => identical results
        by_rid_w = {r.rid: r for r in w.completed}
        by_rid_c = {r.rid: r for r in c.completed}
        assert by_rid_w.keys() == by_rid_c.keys()
        for rid in by_rid_w:
            assert np.array_equal(by_rid_w[rid].found, by_rid_c[rid].found)
            assert np.allclose(by_rid_w[rid].values, by_rid_c[rid].values)
        mw, mc = w.metrics(), c.metrics()
        assert mw.keys == mc.keys
        assert mw.hits == mc.hits                # equal hit rate, exactly
        assert mw.waves == mc.waves              # dense packing held

    def test_submit_dispatches_filled_waves_eagerly(self):
        t = HKVTable.create(capacity=4 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=32, miss_policy="admit",
                                    admission="continuous")
        # 100 keys = 3 full waves dispatched AT SUBMIT + 4 staged keys
        eng.submit(EmbeddingRequest(
            rid=0, keys=np.arange(1, 101, dtype=np.uint64)))
        assert len(eng._flights) == 3
        assert eng._stage_used == 4
        assert not eng.idle
        eng.run_until_drained()
        assert eng.completed[0].done
        assert len(eng.reports) == 4
        assert eng.idle

    def test_poll_reaps_without_dispatching(self):
        t = HKVTable.create(capacity=4 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="admit",
                                    admission="continuous")
        eng.submit(EmbeddingRequest(
            rid=0, keys=np.arange(1, 17, dtype=np.uint64)))   # fills: flies
        assert len(eng._flights) == 1
        # poll never blocks and never dispatches; eventually the wave lands
        import jax
        jax.block_until_ready(eng._flights[0].out[1:])
        rep = eng.poll()
        assert rep is not None and rep.size == 16
        assert not eng._flights
        assert eng.completed and eng.completed[0].done
        # staged-but-unfilled keys stay staged across poll
        eng.submit(EmbeddingRequest(
            rid=1, keys=np.arange(32, 36, dtype=np.uint64)))
        assert eng.poll() is None
        assert eng._stage_used == 4 and not eng.idle
        eng.run_until_drained()
        assert eng.completed[1].done and eng.idle

    def test_slo_split_is_consistent(self):
        t = HKVTable.create(capacity=4 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="admit",
                                    admission="continuous")
        for i in range(6):
            eng.submit(EmbeddingRequest(
                rid=i, keys=np.arange(1 + 16 * i, 17 + 16 * i,
                                      dtype=np.uint64)))
        eng.run_until_drained()
        m = eng.metrics()
        assert m.requests == 6
        for r in eng.completed:
            assert r.t_submit <= r.t_admit <= r.t_done
            assert abs(r.total_latency_s
                       - (r.queue_wait_s + r.service_s)) < 1e-9
        assert m.p99_total_s >= m.p50_total_s >= 0
        assert m.p99_queue_wait_s >= m.p50_queue_wait_s >= 0
        assert m.p99_service_s >= m.p50_service_s > 0

    def test_presubmitted_t_submit_is_honored(self):
        """Open-loop drivers pre-stamp the intended arrival time; the
        engine must not overwrite it (coordinated-omission safety)."""
        t = HKVTable.create(capacity=2 * 128, dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="admit",
                                    admission="continuous")
        req = EmbeddingRequest(rid=0,
                               keys=np.arange(1, 17, dtype=np.uint64))
        req.t_submit = 123.456
        eng.submit(req)
        eng.run_until_drained()
        assert req.t_submit == 123.456

    def test_scheduler_defers_when_staging_spent_the_budget(self):
        from repro.maintenance import MaintenancePolicy, MaintenanceScheduler

        sched = MaintenanceScheduler(MaintenancePolicy(
            every_waves=1, sweep_budget=64))
        t = TieredHKVTable.create(hot_capacity=128, cold_capacity=2 * 128,
                                  dim=DIM)
        eng = OnlineEmbeddingEngine(t, wave_size=16, miss_policy="admit",
                                    scheduler=sched, host_budget_s=1e-12)
        for i in range(5):
            eng.submit(EmbeddingRequest(
                rid=i, keys=np.arange(1 + 16 * i, 17 + 16 * i,
                                      dtype=np.uint64)))
            eng.step()
        # the first-ever step seeds the cost estimate; after that the
        # zero-slack budget defers every interval
        assert sched.totals.runs == 1
        assert sched.totals.deferred == 4


class TestDonation:
    """`donate=True` engines and trainers update the tables they own in
    place; what they serve and train is unchanged."""

    @staticmethod
    def _run(donate, cold_tier):
        t = TieredHKVTable.create(hot_capacity=128, cold_capacity=4 * 128,
                                  dim=DIM, cold_value_tier=cold_tier)
        first = t.hot.state.key_hi
        pub = TablePublisher(t)
        del t
        tr = OnlineTrainer(publisher=pub, publish_every=1, lr=0.5,
                           donate=donate)
        eng = OnlineEmbeddingEngine(pub, wave_size=32, miss_policy="admit",
                                    admission="continuous", donate=donate)
        rng = np.random.default_rng(3)
        reqs, offered = [], []
        for i in range(10):
            keys = rng.integers(0, 600, size=32).astype(np.uint64)
            reqs.append(EmbeddingRequest(rid=i, keys=keys))
            eng.submit(reqs[-1])
            eng.step()
            offered.append(pub.table.hot.state.key_hi)
            if i % 3 == 2:
                tr.train_step(keys, jnp.ones((32, DIM)))
        eng.run_until_drained()
        return reqs, pub.table, tr.table, first, offered

    @pytest.mark.parametrize("cold_tier", ["hbm", "hmem"])
    def test_donating_serve_matches_copying_serve(self, cold_tier):
        want = self._run(False, cold_tier)
        got = self._run(True, cold_tier)
        for a, b in zip(want[0], got[0]):
            assert a.done and b.done
            np.testing.assert_array_equal(a.found, b.found)
            np.testing.assert_array_equal(a.values, b.values)
        for a, b in zip(want[1:3], got[1:3]):
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        first, offered = got[3], got[4]
        assert not want[3].is_deleted()
        assert not any(x.is_deleted() for x in want[4])
        # the engine consumed successors it had offered itself
        assert any(x.is_deleted() for x in offered)
        # the trainer consumed the table it started from, except where
        # that table holds a pinned_host plane on the CPU (no placement
        # is declared there, so it is never donated)
        assert first.is_deleted() == (cold_tier == "hbm")
