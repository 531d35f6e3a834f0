"""Kernel/core parity for the FUSED Pallas updater path (interpret mode).

Same acceptance bar as test_find_kernel.py: BIT-IDENTITY.  The fused
updater kernel (`kernels/update_scan.py`) resolves digest pre-filter +
full-key confirm + dual-bucket merge + in-kernel sparse-optimizer apply +
masked row write-back in ONE launch; it must produce exactly the
(found, values-plane) of

  * the jnp oracle (`kernels.ref.update_scan_ref`),
  * the core jnp reference (`core.ops.update_rows(backend='jnp')` =
    locate + gather + `SparseOptimizer.apply` + assign), and
  * the pre-fusion kernel composition it replaced (digest_scan locate x
    buckets_per_key + gather_rows + host apply + scatter_rows — kept as
    `kernels.ops.update_composed_kernel`),

for ALL FOUR optimizer variants (sgd/sgdm/rowwise_adagrad/adagrad), both
kernel variants (tlp/pipeline), miss lanes under full-table rejection
(cache semantics: un-admitted keys never write), EMPTY padding, odd-n
padding seams, and under jit/vmap.  The launch-count tests pin the PR's
acceptance criterion: the whole gradient step — including through
`OpSession.commit` and `HKVEmbedding.apply_grads` — is ONE kernel launch
(was >= 3 composed).

Bit-identity across eager/jit/batch/row contexts leans on the
``_rounded`` FMA pin in `embedding.sparse_opt` — see that module.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import find as find_mod
from repro.core import merge, ops, table, u64
from repro.core.api import HKVTable
from repro.embedding.dynamic import HKVEmbedding
from repro.embedding.sparse_opt import SparseOptimizer
from repro.kernels import digest_scan as _ds
from repro.kernels import gather as _ga
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels import scatter as _sc
from repro.kernels import update_scan as _upd

EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)

VARIANTS = ("tlp", "pipeline")
OPTIMIZERS = ("sgd", "sgdm", "rowwise_adagrad", "adagrad")


def _opt_cfg(opt_name, *, dual=True, dim=8, capacity=2 * 128, **kw):
    opt = SparseOptimizer(opt_name, lr=0.05)
    cfg = table.HKVConfig(capacity=capacity, dim=dim,
                          buckets_per_key=2 if dual else 1,
                          aux_value_dim=opt.aux_dim(dim), **kw)
    return opt, cfg


def _filled_table(rng, cfg, n_fill):
    """A table with live/empty mix, wide keys, and NON-ZERO aux columns
    (abs-normal, so adagrad accumulators stay in sqrt's domain)."""
    keys = rng.integers(1, 2**50, size=n_fill).astype(np.uint64)
    v = cfg.dim + cfg.aux_value_dim
    vals = jnp.asarray(np.abs(rng.normal(size=(n_fill, v))), jnp.float32)
    state = merge.upsert(table.create(cfg), cfg, u64.from_uint64(keys),
                         vals).state
    return state, keys


def _unique_query(rng, resident, n_hit, n_miss, n_pad):
    """UNIQUE hits + unique wide-key misses + EMPTY padding lanes — the
    updater's precondition (callers dedupe) with the full lane matrix."""
    hits = rng.choice(np.unique(resident), size=n_hit, replace=False)
    misses = np.unique(
        rng.integers(2**50, 2**60, size=4 * n_miss + 4).astype(np.uint64)
    )[:n_miss]
    pads = np.full(n_pad, EMPTY, np.uint64)
    q = np.concatenate([hits, misses, pads])
    rng.shuffle(q)
    return q


def _grads(rng, n, dim):
    return jnp.asarray(rng.normal(size=(n, dim)), jnp.float32)


def _ref_update(state, cfg, k, grads, opt):
    """The jnp oracle assembled exactly as update_rows_kernel feeds it."""
    probe = find_mod.probe_keys(cfg, k)
    b2 = probe.bucket2 if cfg.buckets_per_key == 2 else probe.bucket1
    return ref.update_scan_ref(
        state.digests, state.key_hi, state.key_lo, state.values,
        probe.bucket1, b2, probe.digest.astype(jnp.uint32), k.hi, k.lo,
        probe.valid.astype(jnp.int32), grads, opt, cfg.dim,
        cfg.total_value_dim, use_digest=cfg.use_digest)


def _assert_update_equal(res, want_found, want_values, ctx=""):
    np.testing.assert_array_equal(
        np.asarray(res.found), np.asarray(want_found).astype(bool),
        err_msg=f"{ctx}: found")
    np.testing.assert_array_equal(
        np.asarray(res.state.values), np.asarray(want_values),
        err_msg=f"{ctx}: values")


# =============================================================================
# Raw kernel vs the pure-jnp oracle (ref.update_scan_ref)
# =============================================================================


@pytest.mark.parametrize("dual", [False, True])
@pytest.mark.parametrize("variant", VARIANTS)
def test_update_scan_matches_ref(variant, dual):
    """The kernel in isolation, exact-tile batch (no padding seam)."""
    rng = np.random.default_rng(7 + dual)
    opt, cfg = _opt_cfg("rowwise_adagrad", dual=dual, capacity=4 * 128)
    state, resident = _filled_table(rng, cfg, 400)
    q = _unique_query(rng, resident, 96, 24, 8)
    k = u64.from_uint64(q)
    probe = find_mod.probe_keys(cfg, k)
    b2 = probe.bucket2 if dual else probe.bucket1
    grads = _grads(rng, len(q), cfg.dim)
    args = (state.digests, state.key_hi, state.key_lo, state.values,
            probe.bucket1, b2, probe.digest.astype(jnp.uint32), k.hi, k.lo,
            probe.valid.astype(jnp.int32), grads)
    want_found, want_values = ref.update_scan_ref(
        *args, opt=opt, dim=cfg.dim, width=cfg.total_value_dim)
    got_found, got_values = _upd.update_scan(
        *args, opt=opt, dim=cfg.dim, width=cfg.total_value_dim,
        variant=variant, q_tile=8, use_digest=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_found),
                                  np.asarray(want_found),
                                  err_msg=f"{variant} dual={dual} found")
    np.testing.assert_array_equal(np.asarray(got_values),
                                  np.asarray(want_values),
                                  err_msg=f"{variant} dual={dual} values")


@pytest.mark.parametrize("variant", VARIANTS)
def test_update_scan_use_digest_false_matches_ref(variant):
    """The Exp#3a ablation arm: key-only confirm, no digest pre-filter."""
    rng = np.random.default_rng(13)
    opt, cfg = _opt_cfg("sgd", dual=False, dim=4, use_digest=False)
    state, resident = _filled_table(rng, cfg, 200)
    q = _unique_query(rng, resident, 100, 20, 8)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    want_found, want_values = _ref_update(state, cfg, k, grads, opt)
    res = kops.update_rows_kernel(state, cfg, k, grads, opt, variant=variant,
                                  interpret=True)
    _assert_update_equal(res, want_found, want_values,
                         f"{variant} use_digest=False")


# =============================================================================
# Wrapper: all four optimizers, bit-identical to the jnp reference
# =============================================================================


@pytest.mark.parametrize("opt_name", OPTIMIZERS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_all_optimizers_bit_identical(variant, opt_name):
    """The acceptance criterion: update_rows(backend='kernel') ==
    update_rows(backend='jnp') == update_scan_ref, bit for bit, for every
    optimizer variant."""
    rng = np.random.default_rng(hash(opt_name) % 1000)
    opt, cfg = _opt_cfg(opt_name)
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 48, 12, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    want_found, want_values = _ref_update(state, cfg, k, grads, opt)
    res = kops.update_rows_kernel(state, cfg, k, grads, opt, variant=variant,
                                  interpret=True)
    _assert_update_equal(res, want_found, want_values,
                         f"{variant} {opt_name} vs ref")
    core = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")
    _assert_update_equal(res, core.found, core.state.values,
                         f"{variant} {opt_name} vs core jnp")


@pytest.mark.parametrize("variant", VARIANTS)
def test_odd_n_padding_seams(variant):
    """Pipeline tile remainder + tlp singleton grids: every odd batch size
    agrees with the jnp reference (EMPTY padding never writes)."""
    rng = np.random.default_rng(31)
    opt, cfg = _opt_cfg("rowwise_adagrad", capacity=4 * 128)
    state, resident = _filled_table(rng, cfg, 400)
    for n in (1, 37, 128, 193):
        q = _unique_query(rng, resident, max(1, n - n // 4 - n // 8),
                          n // 4, n // 8)[:n]
        k = u64.from_uint64(q)
        grads = _grads(rng, n, cfg.dim)
        want_found, want_values = _ref_update(state, cfg, k, grads, opt)
        res = kops.update_rows_kernel(state, cfg, k, grads, opt,
                                      variant=variant, interpret=True)
        _assert_update_equal(res, want_found, want_values,
                             f"{variant} n={n}")


@pytest.mark.parametrize("variant", VARIANTS)
def test_fused_matches_composed(variant):
    """The replaced composition (locate + gather_rows + host apply +
    scatter_rows) and the fused pass agree bit-for-bit — the regression
    seam of this PR."""
    rng = np.random.default_rng(41)
    opt, cfg = _opt_cfg("adagrad", dim=16)
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 60, 20, 8)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    fused = kops.update_rows_kernel(state, cfg, k, grads, opt,
                                    variant=variant, interpret=True)
    composed = kops.update_composed_kernel(state, cfg, k, grads, opt,
                                           variant=variant, interpret=True)
    _assert_update_equal(fused, composed.found, composed.state.values,
                         f"{variant} fused vs composed")


@pytest.mark.parametrize("variant", VARIANTS)
def test_miss_lanes_never_write_under_full_rejection(variant):
    """Cache semantics: a batch of entirely non-resident keys (plus EMPTY
    padding) must leave the value plane BITWISE untouched — rejected
    embeddings do not train."""
    rng = np.random.default_rng(53)
    opt, cfg = _opt_cfg("sgdm")
    state, _resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, np.asarray([1], np.uint64), 0, 48, 16)[1:]
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim) * 1e6  # any write would be visible
    res = kops.update_rows_kernel(state, cfg, k, grads, opt, variant=variant,
                                  interpret=True)
    assert not np.asarray(res.found).any()
    np.testing.assert_array_equal(np.asarray(res.state.values),
                                  np.asarray(state.values),
                                  err_msg=f"{variant}: miss lane wrote")


def test_secondary_bucket_residents_train():
    """Drive a dual table to λ=1.0 so some residents live in their
    SECONDARY bucket, then pin that the fused updater trains them."""
    rng = np.random.default_rng(5)
    opt, cfg = _opt_cfg("rowwise_adagrad", dim=4)
    state = table.create(cfg)
    resident = rng.integers(1, 2**50, size=600).astype(np.uint64)
    v = cfg.dim + cfg.aux_value_dim
    for chunk in np.split(resident, 12):
        vals = jnp.asarray(np.abs(rng.normal(size=(len(chunk), v))),
                           jnp.float32)
        state = merge.upsert(state, cfg, u64.from_uint64(chunk), vals).state
    assert float(state.load_factor()) == 1.0
    uq = np.unique(resident)[:128]
    k = u64.from_uint64(uq)
    loc = find_mod.locate(state, cfg, k)
    probe = find_mod.probe_keys(cfg, k)
    in_b2 = np.asarray(loc.found & (loc.bucket == probe.bucket2)
                       & (probe.bucket2 != probe.bucket1))
    assert in_b2.any(), "fill did not produce secondary-bucket residents"
    grads = _grads(rng, len(uq), cfg.dim)
    want_found, want_values = _ref_update(state, cfg, k, grads, opt)
    for variant in VARIANTS:
        res = kops.update_rows_kernel(state, cfg, k, grads, opt,
                                      variant=variant, interpret=True)
        _assert_update_equal(res, want_found, want_values,
                             f"{variant} secondary")
    # the secondary-bucket residents actually changed
    rows_b2 = loc.row[in_b2]
    w = cfg.total_value_dim
    assert (np.asarray(table.tier_gather("hbm", want_values, rows_b2, w))
            != np.asarray(table.tier_gather("hbm", state.values, rows_b2, w))
            ).any()


# =============================================================================
# Dispatch: ops layer, sessions, tiers, jit/vmap
# =============================================================================


def test_ops_updater_backend_parity():
    """ops.update_rows: kernel vs jnp, plus the shared-loc and
    update_scores composed paths, all bit-identical."""
    rng = np.random.default_rng(11)
    opt, cfg = _opt_cfg("rowwise_adagrad")
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 48, 12, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    rj = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")
    rk = ops.update_rows(state, cfg, k, grads, opt, backend="kernel")
    _assert_update_equal(rk, rj.found, rj.state.values, "backend parity")
    # session-shared loc: the composed path against a caller's locate
    loc = find_mod.locate(state, cfg, k)
    rl = ops.update_rows(state, cfg, k, grads, opt, loc=loc,
                         backend="kernel")
    _assert_update_equal(rl, rj.found, rj.state.values, "shared loc")
    # update_scores=True composes through assign's score touch: both
    # backends take the same composed path — value planes still agree
    rsj = ops.update_rows(state, cfg, k, grads, opt, update_scores=True,
                          backend="jnp")
    rsk = ops.update_rows(state, cfg, k, grads, opt, update_scores=True,
                          backend="kernel")
    _assert_update_equal(rsk, rsj.found, rsj.state.values, "update_scores")
    assert np.asarray(rsj.state.score_lo != state.score_lo).any()


def test_updater_backend_validation():
    opt, cfg = _opt_cfg("sgd", dim=4)
    state = table.create(cfg)
    k = u64.from_uint64(np.asarray([1], np.uint64))
    g = jnp.zeros((1, 4), jnp.float32)
    with pytest.raises(ValueError, match="backend"):
        ops.update_rows(state, cfg, k, g, opt, backend="cuda")
    with pytest.raises(ValueError, match="variant"):
        kops.update_rows_kernel(state, cfg, k, g, opt, variant="warp")


def test_hmem_tier_keeps_locate_plus_tier_split():
    """Host-tier value planes keep the §3.6 crossing contract: the kernel
    locates, rows cross via tier_gather/tier_scatter — results identical
    to the jnp path."""
    rng = np.random.default_rng(23)
    opt, cfg = _opt_cfg("rowwise_adagrad", value_tier="hmem")
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 40, 10, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    rj = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")
    rk = ops.update_rows(state, cfg, k, grads, opt, backend="kernel")
    _assert_update_equal(rk, rj.found, rj.state.values, "hmem")


def test_session_row_update_matches_callable_and_unfused():
    """The session surface: a structured RowUpdate commit must equal the
    legacy callable form AND the unfused ops sequence, on both backends."""
    rng = np.random.default_rng(29)
    opt, cfg = _opt_cfg("adagrad")
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 40, 10, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    want = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")
    for backend in ("jnp", "kernel"):
        t = HKVTable.wrap(state, cfg, backend=backend)
        s = t.session()
        r = s.update_rows(k, ops.RowUpdate(opt, grads))
        t2 = s.commit()
        np.testing.assert_array_equal(np.asarray(t2.state.values),
                                      np.asarray(want.state.values),
                                      err_msg=f"{backend} RowUpdate")
        got = r.get()
        np.testing.assert_array_equal(np.asarray(got.found),
                                      np.asarray(want.found))
        s2 = t.session()
        s2.update_rows(k, lambda rows: opt.apply(rows, grads, cfg.dim))
        t3 = s2.commit()
        np.testing.assert_array_equal(np.asarray(t3.state.values),
                                      np.asarray(want.state.values),
                                      err_msg=f"{backend} callable")


def test_session_shared_locate_still_exact():
    """A find before the RowUpdate on the same key batch caches a locate;
    the RowUpdate then takes the composed path against it — still
    bit-identical to the standalone op."""
    rng = np.random.default_rng(37)
    opt, cfg = _opt_cfg("sgd")
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 40, 10, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    want = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")
    t = HKVTable.wrap(state, cfg, backend="kernel")
    s = t.session()
    s.find(k)
    s.update_rows(k, ops.RowUpdate(opt, grads))
    t2 = s.commit()
    np.testing.assert_array_equal(np.asarray(t2.state.values),
                                  np.asarray(want.state.values))


def test_update_under_jit_and_vmap():
    rng = np.random.default_rng(19)
    opt, cfg = _opt_cfg("rowwise_adagrad", dim=4)
    state, resident = _filled_table(rng, cfg, 180)
    q = _unique_query(rng, resident, 40, 10, 4)
    k = u64.from_uint64(q)
    grads = _grads(rng, len(q), cfg.dim)
    want = ops.update_rows(state, cfg, k, grads, opt, backend="jnp")

    # jit: the kernel dispatch inside a traced region
    jup = jax.jit(lambda st, hi, lo, g: ops.update_rows(
        st, cfg, u64.U64(hi, lo), g, opt, backend="kernel"))
    got = jup(state, k.hi, k.lo, grads)
    _assert_update_equal(got, want.found, want.state.values, "jit")

    # vmap: two tables x two query sets mapped over a leading axis — each
    # mapped row must equal its solo run (Pallas adds a batch grid dim)
    state2, resident2 = _filled_table(rng, cfg, 160)
    q2 = _unique_query(rng, resident2, 40, 10, 4)
    k2 = u64.from_uint64(q2)
    grads2 = _grads(rng, len(q2), cfg.dim)

    def run(st, hi, lo, g):
        return ops.update_rows(st, cfg, u64.U64(hi, lo), g, opt,
                               backend="kernel")

    stacked_state = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                                 state, state2)
    vout = jax.vmap(run)(stacked_state, jnp.stack([k.hi, k2.hi]),
                         jnp.stack([k.lo, k2.lo]),
                         jnp.stack([grads, grads2]))
    solo0 = run(state, k.hi, k.lo, grads)
    solo1 = run(state2, k2.hi, k2.lo, grads2)
    for i, solo in enumerate((solo0, solo1)):
        np.testing.assert_array_equal(np.asarray(vout.found[i]),
                                      np.asarray(solo.found),
                                      err_msg=f"vmap row{i} found")
        np.testing.assert_array_equal(np.asarray(vout.state.values[i]),
                                      np.asarray(solo.state.values),
                                      err_msg=f"vmap row{i} values")


# =============================================================================
# apply_grads: the fused front half (dedupe + segment-sum + ONE op)
# =============================================================================


def _manual_apply_grads(emb, table_h, tokens, grads):
    """Per-unique reference computed with numpy dedupe + the jnp op."""
    uniq, inv = np.unique(np.asarray(tokens), return_inverse=True)
    g_sum = np.zeros((len(uniq), emb.dim), np.float32)
    np.add.at(g_sum, inv, np.asarray(grads).reshape(-1, emb.dim))
    keys = emb.keys_of(jnp.asarray(uniq.astype(np.int32)))
    return ops.update_rows(table_h.state, table_h.cfg, keys,
                           jnp.asarray(g_sum), emb.optimizer, backend="jnp")


@pytest.mark.parametrize("backend", ["jnp", "kernel"])
def test_apply_grads_duplicate_heavy_regression(backend):
    """Satellite regression: duplicate-heavy batches must train each
    unique row ONCE with the segment-summed gradient (the compacted
    dedupe), on both backends, bit-identical to the per-unique jnp op."""
    emb = HKVEmbedding(capacity=256, dim=8,
                       optimizer=SparseOptimizer("rowwise_adagrad", lr=0.05),
                       backend=backend)
    t = emb.create()
    rng = np.random.default_rng(61)
    # 12 distinct tokens across 96 lanes: ~8x duplication
    tokens = jnp.asarray(rng.integers(0, 12, 96, dtype=np.int32))
    t, _rows = emb.lookup_train(t, tokens)
    grads = jnp.asarray(rng.normal(size=(96, 8)), jnp.float32)
    want = _manual_apply_grads(emb, t, tokens, grads)
    t2 = emb.apply_grads(t, tokens, grads)
    np.testing.assert_array_equal(np.asarray(t2.state.values),
                                  np.asarray(want.state.values),
                                  err_msg=f"{backend} duplicate-heavy")


def test_apply_grads_extreme_duplication_single_step():
    """All lanes one token: the row must move by exactly ONE optimizer
    step consuming the batch TOTAL (a double-apply would shrink the
    adagrad step visibly)."""
    opt = SparseOptimizer("sgd", lr=0.5)
    emb = HKVEmbedding(capacity=256, dim=4, optimizer=opt, backend="jnp")
    t = emb.create()
    tokens = jnp.full((64,), 7, jnp.int32)
    t, _ = emb.lookup_train(t, tokens)
    before = np.asarray(emb.lookup_serve(t, jnp.asarray([7]))).reshape(4)
    grads = jnp.ones((64, 4), jnp.float32)
    t2 = emb.apply_grads(t, tokens, grads)
    after = np.asarray(emb.lookup_serve(t2, jnp.asarray([7]))).reshape(4)
    np.testing.assert_allclose(after, before - 0.5 * 64.0, rtol=1e-5)


# =============================================================================
# Launch accounting: the whole gradient step is ONE launch
# =============================================================================


class TestLaunchBudget:
    def _counters(self, monkeypatch):
        counts = {"update_scan": 0, "digest_scan": 0, "gather": 0,
                  "scatter": 0}

        def wrap(mod, name, key):
            orig = getattr(mod, name)

            def counting(*a, **kw):
                counts[key] += 1
                return orig(*a, **kw)

            monkeypatch.setattr(mod, name, counting)

        wrap(_upd, "update_scan", "update_scan")
        wrap(_ds, "digest_scan", "digest_scan")
        wrap(_ga, "gather_rows", "gather")
        wrap(_sc, "scatter_rows", "scatter")
        return counts

    @pytest.mark.parametrize("dual", [False, True])
    def test_fused_update_is_one_launch(self, dual, monkeypatch):
        """Old composition: buckets_per_key digest_scan launches + one
        gather + one scatter (>= 3).  Fused: ONE update_scan launch —
        >= 2 eliminated per gradient step (3 in dual mode), the PR's
        acceptance criterion."""
        rng = np.random.default_rng(3)
        opt, cfg = _opt_cfg("rowwise_adagrad", dual=dual, dim=4)
        state, resident = _filled_table(rng, cfg, 150)
        k = u64.from_uint64(np.unique(resident)[:64])
        grads = _grads(rng, 64, cfg.dim)
        counts = self._counters(monkeypatch)
        ops.update_rows(state, cfg, k, grads, opt, backend="kernel")
        assert (counts["update_scan"], counts["digest_scan"],
                counts["gather"], counts["scatter"]) == (1, 0, 0, 0)
        counts.update(update_scan=0)
        kops.update_composed_kernel(state, cfg, k, grads, opt,
                                    interpret=True)
        old = counts["digest_scan"] + counts["gather"] + counts["scatter"]
        assert counts["digest_scan"] == (2 if dual else 1)
        assert counts["gather"] == 1
        assert counts["scatter"] == 1
        assert old >= 3 and old - 1 >= 2  # launches eliminated per step

    def test_session_row_update_is_one_launch(self, monkeypatch):
        """OpSession.commit must NOT pre-locate a structured RowUpdate —
        the whole committed gradient step is one update_scan launch."""
        rng = np.random.default_rng(4)
        opt, cfg = _opt_cfg("sgd", dim=4)
        state, resident = _filled_table(rng, cfg, 150)
        k = u64.from_uint64(np.unique(resident)[:32])
        grads = _grads(rng, 32, cfg.dim)
        counts = self._counters(monkeypatch)
        t = HKVTable.wrap(state, cfg, backend="kernel")
        s = t.session()
        s.update_rows(k, ops.RowUpdate(opt, grads))
        s.commit()
        assert counts == {"update_scan": 1, "digest_scan": 0, "gather": 0,
                          "scatter": 0}

    def test_apply_grads_is_one_launch(self, monkeypatch):
        """End to end: HKVEmbedding.apply_grads = dedupe (XLA) + ONE
        kernel launch (was 3+ and 2x row traffic)."""
        emb = HKVEmbedding(capacity=256, dim=4,
                           optimizer=SparseOptimizer("rowwise_adagrad"),
                           backend="kernel")
        t = emb.create()
        rng = np.random.default_rng(5)
        tokens = jnp.asarray(rng.integers(0, 40, 64, dtype=np.int32))
        t, _ = emb.lookup_train(t, tokens)
        counts = self._counters(monkeypatch)
        emb.apply_grads(t, tokens,
                        jnp.asarray(rng.normal(size=(64, 4)), jnp.float32))
        assert counts == {"update_scan": 1, "digest_scan": 0, "gather": 0,
                          "scatter": 0}
