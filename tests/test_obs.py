"""hkv-obs acceptance: telemetry neutrality, λ-flat counters, trace export.

The ISSUE's four acceptance criteria, plus unit coverage of the obs
building blocks:

  (a) op results are BIT-identical with the telemetry channel on vs off,
      on both backends (jnp and the fused Pallas path in interpret mode);
  (b) `telemetry=None` (the default) adds ZERO kernel launches — the
      trace-time launch accounting of test_find_kernel.py's
      TestLaunchBudget, re-run against the telemetry seam;
  (c) an exp2-style λ sweep reproduces the paper's flat (<5%) probe
      curve FROM THE TELEMETRY CHANNEL ITSELF (probes_per_query);
  (d) `launch/serve.py --trace-out` emits Chrome trace-event JSON that
      round-trips `json.load` with ph/ts/name on every event.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import merge, ops, table, u64
from repro.core.api import HKVTable, normalize_keys
from repro.core.predicates import SweepPredicate
from repro.core.tiered import TieredHKVTable
from repro.embedding.sparse_opt import SparseOptimizer
from repro.kernels import digest_scan as _ds
from repro.kernels import find_scan as _fs
from repro.kernels import gather as _ga
from repro.obs import (MetricsRegistry, NOOP_TRACER, OpTelemetry,
                       TelemetrySink, Tracer, as_tracer)
from repro.obs import telemetry as obs_telemetry
from repro.serving.embedding_engine import EngineMetrics

BACKENDS = ("jnp", "kernel")
DIM = 8
CAP = 8 * 128


def _filled(rng, cfg, n):
    keys = rng.integers(1, 2**50, size=n).astype(np.uint64)
    vals = jnp.asarray(rng.normal(size=(n, cfg.dim)), jnp.float32)
    state = merge.upsert(table.create(cfg), cfg, u64.from_uint64(keys),
                         vals).state
    return state, keys


def _tree_equal(a, b, msg=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), msg
    for i, (x, y) in enumerate(zip(la, lb)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"{msg}: leaf {i} diverged"


# =============================================================================
# (a) bit-identity: telemetry on/off, both backends, every op family
# =============================================================================


@pytest.mark.parametrize("backend", BACKENDS)
def test_op_results_bit_identical_with_telemetry(backend):
    rng = np.random.default_rng(11)
    cfg = table.HKVConfig(capacity=CAP, dim=DIM, buckets_per_key=2)
    state, resident = _filled(rng, cfg, 400)
    hits = rng.choice(resident, size=48)
    misses = rng.integers(2**50, 2**60, size=16).astype(np.uint64)
    k = u64.from_uint64(np.concatenate([hits, misses]))
    vals = jnp.asarray(rng.normal(size=(64, DIM)), jnp.float32)
    opt = SparseOptimizer("sgd", lr=0.5)
    pred = SweepPredicate.score_at_least(1)

    cases = {
        "find": lambda tel: ops.find(state, cfg, k, backend=backend,
                                     telemetry=tel),
        "find_rows": lambda tel: ops.find_rows(state, cfg, k,
                                               backend=backend,
                                               telemetry=tel),
        "find_ptr": lambda tel: ops.find_ptr(state, cfg, k, backend=backend,
                                             telemetry=tel),
        "contains": lambda tel: ops.contains(state, cfg, k, backend=backend,
                                             telemetry=tel),
        "insert_or_assign": lambda tel: ops.insert_or_assign(
            state, cfg, k, vals, backend=backend, telemetry=tel),
        "insert_and_evict": lambda tel: ops.insert_and_evict(
            state, cfg, k, vals, backend=backend, telemetry=tel),
        "find_or_insert": lambda tel: ops.find_or_insert(
            state, cfg, k, vals, backend=backend, telemetry=tel),
        "accum_or_assign": lambda tel: ops.accum_or_assign(
            state, cfg, k, vals, telemetry=tel),
        "update_rows": lambda tel: ops.update_rows(
            state, cfg, k, vals, opt, backend=backend, telemetry=tel),
        "assign": lambda tel: ops.assign(state, cfg, k, vals,
                                         telemetry=tel),
        "erase": lambda tel: ops.erase(state, cfg, k, telemetry=tel),
        "erase_if": lambda tel: ops.erase_if(state, cfg, pred,
                                             backend=backend,
                                             telemetry=tel),
        "evict_if": lambda tel: ops.evict_if(state, cfg, pred, 16,
                                             backend=backend,
                                             telemetry=tel),
    }
    for name, run in cases.items():
        sink = TelemetrySink()
        _tree_equal(run(None), run(sink), f"{name} [{backend}]")
        assert name in sink.by_op, name
        if name in ("erase_if", "evict_if"):   # sweeps: no key lanes
            assert int(np.asarray(sink.total().probed_buckets)) > 0, name
        else:
            assert int(np.asarray(sink.total().lanes)) == 64, name


def test_telemetry_counters_are_correct():
    """Spot-check the counter semantics on a known workload: fresh
    inserts are all misses+inserted; a re-find hits everything."""
    rng = np.random.default_rng(5)
    cfg = table.HKVConfig(capacity=CAP, dim=4, buckets_per_key=2)
    state = table.create(cfg)
    keys = rng.integers(1, 2**40, size=64).astype(np.uint64)
    k = u64.from_uint64(keys)
    vals = jnp.zeros((64, 4), jnp.float32)
    sink = TelemetrySink()
    res = ops.insert_or_assign(state, cfg, k, vals, telemetry=sink)
    up = sink.by_op["insert_or_assign"].to_dict()
    assert up["lanes"] == 64
    assert up["inserted"] + up["evicted"] + up["rejected"] == 64
    assert up["updated"] == 0
    assert up["probed_buckets"] >= 64            # >= one bucket per key
    assert up["second_probe"] == 64              # all lanes missed bucket1
    ops.find(res.state, cfg, k, telemetry=sink)
    fd = sink.by_op["find"].to_dict()
    assert fd["hits"] == 64 and fd["misses"] == 0
    rates = sink.by_op["find"].rates()
    assert rates["hit_rate"] == 1.0
    assert 1.0 <= rates["probes_per_query"] <= 2.0


def test_tiered_telemetry_records_tier_motion():
    t = TieredHKVTable.create(hot_capacity=2 * 128, cold_capacity=8 * 128,
                              dim=4, slots_per_bucket=8)
    sink = TelemetrySink()
    keys = np.arange(1, 400, dtype=np.uint64)
    vals = jnp.ones((len(keys), 4), jnp.float32)
    r = t.insert_or_assign(keys, vals, telemetry=sink)
    assert "insert_and_evict" in sink.by_op       # hot-tier admission op
    assert "tier" in sink.by_op                   # the demotion cascade
    tier = sink.by_op["tier"].to_dict()
    assert tier["demoted"] == int(np.asarray(r.demoted))
    r2 = r.table.find(keys[:16], promote=True, telemetry=sink)
    assert "find" in sink.by_op


# =============================================================================
# (b) zero launches with telemetry off — and none added when on
# =============================================================================


class TestLaunchNeutrality:
    def _counters(self, monkeypatch):
        counts = {"find_scan": 0, "digest_scan": 0, "gather": 0}

        def wrap(mod, name, key):
            orig = getattr(mod, name)

            def counting(*a, **kw):
                counts[key] += 1
                return orig(*a, **kw)

            monkeypatch.setattr(mod, name, counting)

        wrap(_fs, "find_scan", "find_scan")
        wrap(_ds, "digest_scan", "digest_scan")
        wrap(_ga, "gather_rows", "gather")
        return counts

    def test_telemetry_none_adds_zero_launches(self, monkeypatch):
        """Kernel-backed find with the default telemetry=None stays ONE
        fused launch (the test_find_kernel.py pin, re-asserted across
        the telemetry seam)."""
        rng = np.random.default_rng(3)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
        state, resident = _filled(rng, cfg, 200)
        k = u64.from_uint64(resident[:64])
        counts = self._counters(monkeypatch)
        ops.find(state, cfg, k, backend="kernel", telemetry=None)
        assert (counts["find_scan"], counts["digest_scan"],
                counts["gather"]) == (1, 0, 0)

    def test_telemetry_on_adds_zero_launches(self, monkeypatch):
        """The observers are pure jnp over already-fetched planes — a
        live sink must not change the kernel launch set either."""
        rng = np.random.default_rng(3)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
        state, resident = _filled(rng, cfg, 200)
        k = u64.from_uint64(resident[:64])
        counts = self._counters(monkeypatch)
        ops.find(state, cfg, k, backend="kernel", telemetry=TelemetrySink())
        assert (counts["find_scan"], counts["digest_scan"],
                counts["gather"]) == (1, 0, 0)

    def test_telemetry_none_jaxpr_is_unchanged(self):
        """Zero jaxpr growth: spelling out telemetry=None traces to the
        exact equation list of the kwarg-free call."""
        t = HKVTable.create(capacity=2 * 128, dim=4, backend="kernel")
        k = normalize_keys(np.arange(1, 17, dtype=np.uint64))

        def plain(tt, kh, kl):
            r = tt.find(u64.U64(kh, kl))
            return r.values, r.found

        def spelled(tt, kh, kl):
            r = tt.find(u64.U64(kh, kl), telemetry=None)
            return r.values, r.found

        ja = jax.make_jaxpr(plain)(t, k.hi, k.lo)
        jb = jax.make_jaxpr(spelled)(t, k.hi, k.lo)
        assert len(ja.jaxpr.eqns) == len(jb.jaxpr.eqns)


# =============================================================================
# (c) the λ-stability claim, measured from the telemetry channel
# =============================================================================


def test_probe_counter_flat_across_load_factor():
    """exp1/exp2's headline, from the device counters: probes_per_query
    for resident-key finds varies < 5% from λ=0.25 to λ=0.95 (HKV probes
    a structurally constant bucket set; occupancy never grows it)."""
    cfg = table.HKVConfig(capacity=32 * 128, dim=4, buckets_per_key=2)
    probes = {}
    for lam in (0.25, 0.5, 0.75, 0.95):
        rng = np.random.default_rng(17)   # same stream per λ point
        n = int(lam * cfg.capacity)
        state, resident = _filled(rng, cfg, n)
        q = u64.from_uint64(rng.choice(resident, size=512))
        sink = TelemetrySink()
        ops.find(state, cfg, q, telemetry=sink)
        probes[lam] = sink.by_op["find"].rates()["probes_per_query"]
    lo, hi = min(probes.values()), max(probes.values())
    assert (hi - lo) / lo < 0.05, f"probe curve not λ-flat: {probes}"


# =============================================================================
# (d) serve.py --trace-out emits loadable Chrome trace JSON
# =============================================================================


def test_serve_trace_out_round_trips(tmp_path):
    trace = tmp_path / "trace.json"
    metrics = tmp_path / "metrics.prom"
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--smoke",
         "--waves", "4", "--wave-size", "64", "--maintain",
         "--trace-out", str(trace), "--metrics-out", str(metrics)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr
    doc = json.load(open(trace))
    evs = doc["traceEvents"]
    assert evs, "trace is empty"
    for ev in evs:
        assert "ph" in ev and "ts" in ev and "name" in ev, ev
    names = {ev["name"] for ev in evs}
    assert "wave.dispatch" in names and "wave.reap" in names
    assert "engine.submit" in names and "request" in names
    assert "maintenance.run" in names
    # durations are µs floats; complete spans carry them
    assert all("dur" in ev for ev in evs if ev["ph"] == "X")
    text = open(metrics).read()
    assert "# TYPE hkv_engine_waves gauge" in text
    assert "hkv_maintenance_deferred" in text
    assert "hkv_hot_load_factor" in text and "hkv_cold_load_factor" in text
    assert "deferred=" in r.stdout       # the SLO summary satellite


# =============================================================================
# Unit coverage: tracer, sink, registry, EngineMetrics.zero
# =============================================================================


def test_tracer_spans_and_instants():
    tr = Tracer()
    with tr.span("outer", tag="a"):
        tr.instant("mark", n=1)
        with tr.span("inner"):
            pass
    tr.complete_abs("abs", tr._t0, tr._t0 + 0.5, rid=7)
    assert len(tr) == 4
    doc = tr.to_chrome()
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    assert by_name["outer"]["ph"] == "X"
    assert by_name["outer"]["args"] == {"tag": "a"}
    assert abs(by_name["abs"]["dur"] - 5e5) < 1e3   # 0.5 s in µs
    # spans nest: inner lies within outer
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]


def test_noop_tracer_absorbs_everything():
    assert as_tracer(None) is NOOP_TRACER
    t = Tracer()
    assert as_tracer(t) is t
    assert not NOOP_TRACER and len(NOOP_TRACER) == 0
    with NOOP_TRACER.span("x"):
        NOOP_TRACER.instant("y")
    NOOP_TRACER.complete("z", 0.0, 1.0)
    NOOP_TRACER.complete_abs("z", 0.0, 1.0)
    assert NOOP_TRACER.to_chrome() == {"traceEvents": []}
    with pytest.raises(RuntimeError):
        NOOP_TRACER.save("/tmp/nope.json")


def test_op_telemetry_pytree_algebra():
    a = OpTelemetry.of(lanes=4, hits=3, probed_buckets=8)
    b = OpTelemetry.of(lanes=2, misses=2, probed_buckets=2)
    m = a.merge(b).to_dict()
    assert m["lanes"] == 6 and m["hits"] == 3 and m["probed_buckets"] == 10
    z = OpTelemetry.zero().to_dict()
    assert all(v == 0 for v in z.values())
    # rates guard against zero denominators
    r = OpTelemetry.zero().rates()
    assert r["probes_per_query"] == 0.0 and r["hit_rate"] == 0.0
    # the pytree flattens to jax-able leaves (jit/psum compatibility)
    leaves = jax.tree_util.tree_leaves(a)
    assert len(leaves) == len(OpTelemetry._fields)


def test_sink_accumulates_and_snapshots():
    sink = TelemetrySink()
    assert bool(sink)
    sink.record("find", OpTelemetry.of(lanes=4, hits=2))
    sink.record("find", OpTelemetry.of(lanes=4, hits=4))
    sink.record("erase", OpTelemetry.of(lanes=1, swept=1))
    assert sink.calls == {"find": 2, "erase": 1}
    snap = sink.snapshot()
    assert snap["find"]["hits"] == 6
    tot = sink.total().to_dict()
    assert tot["lanes"] == 9 and tot["swept"] == 1


def test_metrics_registry_prometheus_exposition():
    reg = MetricsRegistry()
    reg.set("hkv_demo_total", 3, help="a demo counter")
    reg.set("hkv_demo_rate", 0.25)
    reg.inc("hkv_demo_total", 2)
    text = reg.prometheus()
    assert "# HELP hkv_demo_total a demo counter" in text
    assert "# TYPE hkv_demo_total gauge" in text
    assert "\nhkv_demo_total 5\n" in text
    assert "hkv_demo_rate 0.25" in text
    assert text.endswith("\n")
    sink = TelemetrySink()
    sink.record("find", OpTelemetry.of(lanes=8, hits=6, probed_buckets=8))
    reg.observe_telemetry(sink)
    assert reg.get("hkv_op_find_hits") == 6.0
    assert reg.get("hkv_op_find_probes_per_query") == 1.0
    assert reg.get("hkv_op_find_calls") == 1.0
    j = json.loads(reg.to_json(run="t"))
    assert j["schema"] == "hkv-metrics/v1" and j["run"] == "t"
    assert j["gauges"]["hkv_op_find_hits"] == 6.0


def test_engine_metrics_zero_is_well_formed():
    z = EngineMetrics.zero()
    assert z.waves == 0 and z.requests == 0
    assert z.p99_latency_s == 0.0 and z.p99_total_s == 0.0
    assert len(z) == len(EngineMetrics._fields)
    # the engine returns it for empty runs
    from repro.serving.embedding_engine import OnlineEmbeddingEngine
    t = HKVTable.create(capacity=2 * 128, dim=4)
    eng = OnlineEmbeddingEngine(t, wave_size=8)
    assert eng.metrics() == z


def test_registry_observes_engine_scheduler_and_stats():
    from repro.maintenance.scheduler import MaintenanceTotals
    reg = MetricsRegistry()
    reg.observe_engine(EngineMetrics.zero())
    reg.observe_maintenance(MaintenanceTotals(
        runs=3, expired=1, demoted=2, dropped=0, skipped_offers=1,
        time_s=0.5, deferred=4))
    t = HKVTable.create(capacity=2 * 128, dim=4)
    reg.observe_table(t.stats(), tier="hot")
    assert reg.get("hkv_engine_waves") == 0.0
    assert reg.get("hkv_maintenance_deferred") == 4.0
    assert reg.get("hkv_hot_capacity") == 256.0
    assert reg.get("hkv_hot_load_factor") == 0.0


def test_tracer_spans_land_in_the_profiler_trace_on_its_clock(tmp_path):
    """A live Tracer's span is also the profiler annotation hkv.<name>, and
    the Chrome export stamps it on the profiler's clock (Unix-epoch µs)."""
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("wave.dispatch"):
            jnp.arange(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    data = ProfileData.from_file(str(sorted(tmp_path.rglob("*.xplane.pb"))[-1]))
    planes = list(data.planes)
    start_ns = next(int(dict(p.stats)["profile_start_time"]) for p in planes
                    if p.name == "Task Environment")
    spans = [ev for p in planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name == "hkv.wave.dispatch"]
    assert len(spans) == 1
    (chrome,) = [e for e in tr.to_chrome()["traceEvents"]
                 if e["name"] == "wave.dispatch"]
    assert abs((start_ns + spans[0].start_ns) / 1e3 - chrome["ts"]) < 1e3
