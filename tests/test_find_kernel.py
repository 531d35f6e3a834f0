"""Kernel/core parity for the FUSED Pallas find path (interpret mode).

Same acceptance bar as test_upsert_kernel.py / test_sweep_kernel.py:
BIT-IDENTITY.  The fused kernel (`kernels/find_scan.py`) resolves digest
pre-filter + full-key confirm + dual-bucket merge + score readout + value
gather in ONE launch; it must produce exactly the (found, bucket, slot,
scores, values) of

  * the jnp reference (`core.find.locate` + `gather_values` + the score
    readout in `core.ops.find`/`find_rows`), and
  * the pre-fusion composition it replaced (digest_scan locate x
    buckets_per_key + gather_rows — kept as
    `kernels.ops.find_composed_kernel`),

for both variants (tlp / pipeline), masked/EMPTY-padded lanes, duplicate
keys in batch, wide (>32-bit) keys, hit/miss/secondary-bucket-collision
cases, and under jit/vmap wrapping.  The launch-count tests pin the
acceptance criterion that fusion eliminates >= 1 kernel launch per find.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import find as find_mod
from repro.core import merge, ops, table, u64
from repro.core.api import HKVTable
from repro.kernels import digest_scan as _ds
from repro.kernels import find_scan as _fs
from repro.kernels import gather as _ga
from repro.kernels import ops as kops
from repro.kernels import ref

EMPTY = np.uint64(0xFFFFFFFFFFFFFFFF)

VARIANTS = ("tlp", "pipeline")


def _query_batch(rng, resident, n_hit, n_miss, n_pad, dup_frac=0.25):
    """Hits drawn from `resident` (with duplicates), wide-key misses,
    EMPTY-sentinel padding lanes — the full parity matrix in one batch."""
    hits = rng.choice(resident, size=n_hit)
    ndup = int(n_hit * dup_frac)
    if ndup:
        hits[rng.integers(0, n_hit, size=ndup)] = rng.choice(hits, size=ndup)
    misses = rng.integers(2**50, 2**60, size=n_miss).astype(np.uint64)
    pads = np.full(n_pad, EMPTY, np.uint64)
    q = np.concatenate([hits, misses, pads])
    rng.shuffle(q)
    return q


def _filled_table(rng, cfg, n_fill):
    """A table with live/empty mix and wide keys (>= 2**32)."""
    keys = rng.integers(1, 2**50, size=n_fill).astype(np.uint64)
    vals = jnp.asarray(rng.normal(size=(n_fill, cfg.dim)), jnp.float32)
    state = merge.upsert(table.create(cfg), cfg, u64.from_uint64(keys),
                         vals).state
    return state, keys


def _ref_find(state, cfg, keys):
    """The jnp oracle assembled exactly as core.ops.find/find_rows do."""
    loc = find_mod.locate(state, cfg, keys)
    rows = find_mod.gather_values(state, loc, None, cfg)
    shi = jnp.where(loc.found, state.score_hi[loc.bucket, loc.slot], 0)
    slo = jnp.where(loc.found, state.score_lo[loc.bucket, loc.slot], 0)
    return loc, rows, shi, slo


def _assert_fused_equal(r, state, cfg, keys, ctx=""):
    loc, rows, shi, slo = _ref_find(state, cfg, keys)
    np.testing.assert_array_equal(np.asarray(r.found), np.asarray(loc.found),
                                  err_msg=f"{ctx}: found")
    np.testing.assert_array_equal(np.asarray(r.bucket), np.asarray(loc.bucket),
                                  err_msg=f"{ctx}: bucket")
    np.testing.assert_array_equal(np.asarray(r.slot), np.asarray(loc.slot),
                                  err_msg=f"{ctx}: slot")
    np.testing.assert_array_equal(np.asarray(r.row), np.asarray(loc.row),
                                  err_msg=f"{ctx}: row")
    np.testing.assert_array_equal(np.asarray(r.values), np.asarray(rows),
                                  err_msg=f"{ctx}: values")
    np.testing.assert_array_equal(np.asarray(r.score_hi), np.asarray(shi),
                                  err_msg=f"{ctx}: score_hi")
    np.testing.assert_array_equal(np.asarray(r.score_lo), np.asarray(slo),
                                  err_msg=f"{ctx}: score_lo")


def _raw_find(variant, *args, width, use_digest=True):
    """The raw fused-find kernel over one tile-divisible batch."""
    n = args[6].shape[0]
    return _fs.find_scan(*args, width=width, variant=variant,
                         q_tile=8 if n % 8 == 0 else n,
                         use_digest=use_digest, interpret=True)


# =============================================================================
# Raw kernel vs the pure-jnp oracle (ref.find_scan_ref)
# =============================================================================


@pytest.mark.parametrize("dual", [False, True])
@pytest.mark.parametrize("variant", VARIANTS)
def test_find_scan_matches_ref(variant, dual):
    """The kernel in isolation, exact-tile batch (no padding seam)."""
    rng = np.random.default_rng(7 + dual)
    cfg = table.HKVConfig(capacity=4 * 128, dim=8,
                          buckets_per_key=2 if dual else 1)
    state, resident = _filled_table(rng, cfg, 400)
    q = _query_batch(rng, resident, 96, 24, 8)
    k = u64.from_uint64(q)
    probe = find_mod.probe_keys(cfg, k)
    b2 = probe.bucket2 if dual else probe.bucket1
    args = (state.digests, state.key_hi, state.key_lo, state.score_hi,
            state.score_lo, state.values, probe.bucket1, b2,
            probe.digest.astype(jnp.uint32), k.hi, k.lo)
    want = ref.find_scan_ref(*args, cfg.total_value_dim)
    got = _raw_find(variant, *args, width=cfg.total_value_dim)
    for g, w, name in zip(got, want,
                          ("found", "sel", "slot", "shi", "slo", "vals")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{variant} dual={dual} {name}")


@pytest.mark.parametrize("variant", VARIANTS)
def test_find_scan_use_digest_false_matches_ref(variant):
    """The Exp#3a ablation arm: key-only compare, no digest pre-filter."""
    rng = np.random.default_rng(13)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, use_digest=False)
    state, resident = _filled_table(rng, cfg, 200)
    q = _query_batch(rng, resident, 100, 20, 8)
    k = u64.from_uint64(q)
    probe = find_mod.probe_keys(cfg, k)
    args = (state.digests, state.key_hi, state.key_lo, state.score_hi,
            state.score_lo, state.values, probe.bucket1, probe.bucket1,
            probe.digest.astype(jnp.uint32), k.hi, k.lo)
    want = ref.find_scan_ref(*args, cfg.total_value_dim, use_digest=False)
    got = _raw_find(variant, *args, width=cfg.total_value_dim,
                    use_digest=False)
    for g, w, name in zip(got, want,
                          ("found", "sel", "slot", "shi", "slo", "vals")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{variant} {name}")
    # the fused wrapper honors cfg.use_digest end-to-end
    r = kops.find_fused_kernel(state, cfg, k, variant=variant, interpret=True)
    _assert_fused_equal(r, state, cfg, k, f"{variant} use_digest=False")


# =============================================================================
# Wrapper vs the core jnp reference AND the old composition
# =============================================================================


@pytest.mark.parametrize("dual", [False, True])
@pytest.mark.parametrize("variant", VARIANTS)
def test_fused_wrapper_bit_identical_to_core(variant, dual):
    """find_fused_kernel vs locate+gather+scores, odd batch sizes included
    (the pipeline variant's padding seam)."""
    rng = np.random.default_rng(31 * (1 + dual))
    cfg = table.HKVConfig(capacity=4 * 128, dim=8,
                          buckets_per_key=2 if dual else 1, score_policy="lfu")
    state, resident = _filled_table(rng, cfg, 700)  # λ beyond 1.0: evictions
    for n in (1, 37, 128, 193):
        q = _query_batch(rng, resident, max(1, n - n // 4 - n // 8),
                         n // 4, n // 8)[:n]
        k = u64.from_uint64(q)
        r = kops.find_fused_kernel(state, cfg, k, variant=variant,
                                   interpret=True)
        _assert_fused_equal(r, state, cfg, k,
                            f"{variant} dual={dual} n={n}")


@pytest.mark.parametrize("dual", [False, True])
@pytest.mark.parametrize("variant", VARIANTS)
def test_fused_matches_old_composition(variant, dual):
    """The replaced pair (digest_scan locate + gather_rows) and the fused
    pass agree bit-for-bit — the regression seam of this PR."""
    rng = np.random.default_rng(41 + dual)
    cfg = table.HKVConfig(capacity=2 * 128, dim=16,
                          buckets_per_key=2 if dual else 1)
    state, resident = _filled_table(rng, cfg, 300)
    q = _query_batch(rng, resident, 80, 30, 18)
    k = u64.from_uint64(q)
    v_new, f_new = kops.find_kernel(state, cfg, k, variant=variant,
                                    interpret=True)
    v_old, f_old = kops.find_composed_kernel(state, cfg, k, variant=variant,
                                             interpret=True)
    np.testing.assert_array_equal(np.asarray(f_new), np.asarray(f_old))
    np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_old))


def _raw_vs_ref(state, cfg, k, variant, ctx, q_tile=None):
    """The raw kernel, against `ref.find_scan_ref`, over the batch padded
    and tiled as the wrapper plans it (or in tiles of `q_tile`)."""
    n = k.hi.shape[0]
    q_tile, npad = kops._plan(n) if q_tile is None else (q_tile, n)
    probe = find_mod.probe_keys(cfg, k)
    b2 = probe.bucket2 if cfg.buckets_per_key == 2 else probe.bucket1
    pad = lambda x, fill=0: kops._pad_to(x, npad, fill)
    args = (state.digests, state.key_hi, state.key_lo, state.score_hi,
            state.score_lo, state.values, pad(probe.bucket1), pad(b2),
            pad(probe.digest.astype(jnp.uint32)), pad(k.hi, u64.EMPTY_HI),
            pad(k.lo, u64.EMPTY_LO))
    want = ref.find_scan_ref(*args, cfg.total_value_dim)
    got = _fs.find_scan(*args, width=cfg.total_value_dim, variant=variant,
                        q_tile=q_tile, use_digest=True, interpret=True)
    for g, w, name in zip(got, want,
                          ("found", "sel", "slot", "shi", "slo", "vals")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{ctx} {name}")


def _dual_table_at_full_load(rng):
    """A small dual-bucket table driven to λ=1.0 by chunked inserts:
    load-balance picks the emptier candidate per step, so once primaries
    fill, later keys land in their SECONDARY bucket."""
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
    state = table.create(cfg)
    resident = rng.integers(1, 2**50, size=600).astype(np.uint64)
    for chunk in np.split(resident, 12):
        vals = jnp.asarray(rng.normal(size=(len(chunk), cfg.dim)),
                           jnp.float32)
        state = merge.upsert(state, cfg, u64.from_uint64(chunk), vals).state
    return state, cfg, resident


@pytest.mark.parametrize("n", [8, 24, 136])
@pytest.mark.parametrize("variant", VARIANTS)
def test_ring_edges_bit_identical(variant, n):
    """The wrapper's tiles: q_tile 8, 24, and 128 over two tiles, the
    second mostly padding.  Every query retired in-loop or after the loop
    must match both oracles."""
    rng = np.random.default_rng(100 + n)
    cfg = table.HKVConfig(capacity=4 * 128, dim=8, buckets_per_key=2)
    state, resident = _filled_table(rng, cfg, 600)
    q = _query_batch(rng, resident, n - n // 4 - n // 8, n // 4, n // 8)[:n]
    k = u64.from_uint64(q)
    r = kops.find_fused_kernel(state, cfg, k, variant=variant,
                               interpret=True)
    _assert_fused_equal(r, state, cfg, k, f"{variant} n={n}")
    _raw_vs_ref(state, cfg, k, variant, f"{variant} n={n}")


@pytest.mark.parametrize("q_tile", [1, 3])
@pytest.mark.parametrize("variant", VARIANTS)
def test_ring_odd_tiles_match_ref(variant, q_tile):
    """Raw kernel tiles shorter than, and no multiple of, the rings' two
    slots: with one query a tile, only the after-loop retire runs."""
    rng = np.random.default_rng(71 + q_tile)
    cfg = table.HKVConfig(capacity=4 * 128, dim=8, buckets_per_key=2)
    state, resident = _filled_table(rng, cfg, 600)
    q = _query_batch(rng, resident, 6 * q_tile, q_tile, q_tile)
    _raw_vs_ref(state, cfg, u64.from_uint64(q), variant,
                f"{variant} q_tile={q_tile}", q_tile=q_tile)


@pytest.mark.parametrize("variant", VARIANTS)
def test_ring_tail_misses_and_second_bucket_hits(variant):
    """Misses and secondary-bucket hits in the last 16 positions of each
    tile, the last of them retired after the loop, over two tiles of 128."""
    rng = np.random.default_rng(57)
    state, cfg, resident = _dual_table_at_full_load(rng)
    keys = np.unique(resident)
    k = u64.from_uint64(keys)
    loc = find_mod.locate(state, cfg, k)
    probe = find_mod.probe_keys(cfg, k)
    found = np.asarray(loc.found)
    in_b2 = found & np.asarray((loc.bucket == probe.bucket2)
                               & (probe.bucket2 != probe.bucket1))
    second, first = keys[in_b2], keys[found & ~in_b2]
    assert len(second) >= 16, "fill did not produce secondary residents"
    misses = rng.integers(2**50, 2**60, size=16).astype(np.uint64)
    tail = np.stack([misses, rng.choice(second, 16)], axis=1).reshape(-1)
    tiles = []
    for t in range(2):
        head = rng.choice(first, 128 - 16)
        tiles.append(np.concatenate([head, tail[16 * t:16 * (t + 1)]]))
    q = u64.from_uint64(np.concatenate(tiles))
    assert kops._plan(256) == (128, 256)
    r = kops.find_fused_kernel(state, cfg, q, variant=variant,
                               interpret=True)
    _assert_fused_equal(r, state, cfg, q, f"{variant} ring tail")
    _raw_vs_ref(state, cfg, q, variant, f"{variant} ring tail")


@pytest.mark.parametrize("variant", VARIANTS)
def test_ring_across_chunk_boundary(variant):
    """A batch of CHUNK + 136 keys runs as two kernel calls of CHUNK
    queries (the second mostly padding): each call starts and drains its
    rings on its own."""
    rng = np.random.default_rng(61)
    cfg = table.HKVConfig(capacity=4 * 128, dim=8, buckets_per_key=2)
    state, resident = _filled_table(rng, cfg, 600)
    n = kops.CHUNK + 136
    q = _query_batch(rng, resident, n - n // 4 - n // 8, n // 4, n // 8)[:n]
    k = u64.from_uint64(q)
    assert kops._plan(n) == (128, 2 * kops.CHUNK)
    r = kops.find_fused_kernel(state, cfg, k, variant=variant,
                               interpret=True)
    _assert_fused_equal(r, state, cfg, k, f"{variant} n={n}")


def test_secondary_bucket_hits_are_exercised_and_identical():
    """Drive a small dual table to λ=1.0 so some residents live in their
    SECONDARY bucket, then pin that the fused path resolves them."""
    state, cfg, resident = _dual_table_at_full_load(np.random.default_rng(5))
    assert float(state.load_factor()) == 1.0
    k = u64.from_uint64(np.unique(resident))
    loc = find_mod.locate(state, cfg, k)
    probe = find_mod.probe_keys(cfg, k)
    in_b2 = np.asarray(loc.found & (loc.bucket == probe.bucket2)
                       & (probe.bucket2 != probe.bucket1))
    assert in_b2.any(), "fill did not produce secondary-bucket residents"
    for variant in VARIANTS:
        r = kops.find_fused_kernel(state, cfg, k, variant=variant,
                                   interpret=True)
        _assert_fused_equal(r, state, cfg, k, f"{variant} secondary")


# =============================================================================
# Dispatch: ops-layer backends, sessions, tiers, jit/vmap
# =============================================================================


def test_ops_reader_backend_parity():
    rng = np.random.default_rng(11)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
    state, resident = _filled_table(rng, cfg, 300)
    q = _query_batch(rng, resident, 60, 20, 4)
    k = u64.from_uint64(q)
    fj = ops.find(state, cfg, k, backend="jnp")
    fk = ops.find(state, cfg, k, backend="kernel")
    for f in fj._fields:
        np.testing.assert_array_equal(np.asarray(getattr(fj, f)),
                                      np.asarray(getattr(fk, f)),
                                      err_msg=f"find.{f}")
    rj = ops.find_rows(state, cfg, k, backend="jnp")
    rk = ops.find_rows(state, cfg, k, backend="kernel")
    for f in rj._fields:
        np.testing.assert_array_equal(np.asarray(getattr(rj, f)),
                                      np.asarray(getattr(rk, f)),
                                      err_msg=f"find_rows.{f}")
    np.testing.assert_array_equal(
        np.asarray(ops.contains(state, cfg, k, backend="jnp")),
        np.asarray(ops.contains(state, cfg, k, backend="kernel")))
    lj = ops.find_ptr(state, cfg, k, backend="jnp")
    lk = ops.find_ptr(state, cfg, k, backend="kernel")
    for f in lj._fields:
        np.testing.assert_array_equal(np.asarray(getattr(lj, f)),
                                      np.asarray(getattr(lk, f)),
                                      err_msg=f"find_ptr.{f}")


def test_reader_backend_validation():
    cfg = table.HKVConfig(capacity=128, dim=4)
    state = table.create(cfg)
    k = u64.from_uint64(np.asarray([1], np.uint64))
    with pytest.raises(ValueError, match="backend"):
        ops.find(state, cfg, k, backend="cuda")
    with pytest.raises(ValueError, match="variant"):
        kops.find_fused_kernel(state, cfg, k, variant="warp")


def test_hmem_tier_falls_back_to_tier_gather():
    """Host-tier value planes keep the §3.6 crossing contract: the kernel
    locates, tier_gather moves rows — results identical to jnp."""
    rng = np.random.default_rng(23)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, value_tier="hmem")
    state, resident = _filled_table(rng, cfg, 200)
    q = _query_batch(rng, resident, 50, 10, 4)
    k = u64.from_uint64(q)
    r = kops.find_fused_kernel(state, cfg, k, interpret=True)
    _assert_fused_equal(r, state, cfg, k, "hmem")
    fj = ops.find_rows(state, cfg, k, backend="jnp")
    fk = ops.find_rows(state, cfg, k, backend="kernel")
    for f in fj._fields:
        np.testing.assert_array_equal(np.asarray(getattr(fj, f)),
                                      np.asarray(getattr(fk, f)),
                                      err_msg=f"hmem find_rows.{f}")


def test_fused_find_under_jit_and_vmap():
    rng = np.random.default_rng(19)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4, buckets_per_key=2)
    state, resident = _filled_table(rng, cfg, 300)
    tk = HKVTable.wrap(state, cfg, backend="kernel")
    q = _query_batch(rng, resident, 50, 10, 4)
    k = u64.from_uint64(q)

    # jit: the handle path (fused pass inside the traced region)
    jfind = jax.jit(lambda t, hi, lo: t.find(u64.U64(hi, lo)))
    got = jfind(tk, k.hi, k.lo)
    want = tk.with_backend("jnp").find(k)
    for f in want._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f"jit find.{f}")

    # vmap: map the raw kernel over a stacked query axis (Pallas adds a
    # grid dim); each mapped row must equal its solo run
    probe = find_mod.probe_keys(cfg, k)
    args = lambda sl: (probe.bucket1[sl], probe.bucket2[sl],
                       probe.digest.astype(jnp.uint32)[sl], k.hi[sl],
                       k.lo[sl])
    half = len(q) // 2
    stacked = tuple(jnp.stack([a, b]) for a, b in
                    zip(args(slice(0, half)), args(slice(half, 2 * half))))
    fn = lambda b1, b2, qd, qh, ql: _raw_find(
        "tlp", state.digests, state.key_hi, state.key_lo, state.score_hi,
        state.score_lo, state.values, b1, b2, qd, qh, ql,
        width=cfg.total_value_dim)
    vout = jax.vmap(fn)(*stacked)
    solo0 = fn(*args(slice(0, half)))
    solo1 = fn(*args(slice(half, 2 * half)))
    for i, name in enumerate(("found", "sel", "slot", "shi", "slo", "vals")):
        np.testing.assert_array_equal(np.asarray(vout[i][0]),
                                      np.asarray(solo0[i]),
                                      err_msg=f"vmap row0 {name}")
        np.testing.assert_array_equal(np.asarray(vout[i][1]),
                                      np.asarray(solo1[i]),
                                      err_msg=f"vmap row1 {name}")


# =============================================================================
# Launch accounting: fusion eliminates >= 1 launch per find
# =============================================================================


class TestLaunchBudget:
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

    @pytest.mark.parametrize("dual", [False, True])
    def test_fused_find_is_one_launch(self, dual, monkeypatch):
        """Old composition: buckets_per_key digest_scan launches + one
        gather launch.  Fused: ONE find_scan launch — >= 1 eliminated
        (2 in dual mode), the PR's acceptance criterion."""
        rng = np.random.default_rng(3)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4,
                              buckets_per_key=2 if dual else 1)
        state, resident = _filled_table(rng, cfg, 200)
        k = u64.from_uint64(resident[:64])
        counts = self._counters(monkeypatch)
        ops.find(state, cfg, k, backend="kernel")
        assert (counts["find_scan"], counts["digest_scan"],
                counts["gather"]) == (1, 0, 0)
        counts.update(find_scan=0)
        kops.find_composed_kernel(state, cfg, k, interpret=True)
        old = counts["digest_scan"] + counts["gather"]
        assert counts["digest_scan"] == (2 if dual else 1)
        assert counts["gather"] == 1
        assert old - 1 >= 1  # launches eliminated per find

    def test_find_ptr_stays_metadata_only(self, monkeypatch):
        """The pointer path must NOT ride the fused pass (no value
        traffic) — it takes the digest_scan locate."""
        rng = np.random.default_rng(4)
        cfg = table.HKVConfig(capacity=2 * 128, dim=4)
        state, resident = _filled_table(rng, cfg, 100)
        k = u64.from_uint64(resident[:32])
        counts = self._counters(monkeypatch)
        ops.find_ptr(state, cfg, k, backend="kernel")
        assert counts == {"find_scan": 0, "digest_scan": 1, "gather": 0}


# =============================================================================
# find_many: batched multi-table lookup in one launch
# =============================================================================


@pytest.mark.parametrize("variant", VARIANTS)
def test_find_many_matches_per_table_finds(variant):
    rng = np.random.default_rng(29)
    cfg = table.HKVConfig(capacity=2 * 128, dim=8, buckets_per_key=2)
    states, keysets = [], []
    for _ in range(3):
        state, resident = _filled_table(rng, cfg, 250)
        states.append(state)
        keysets.append(u64.from_uint64(
            _query_batch(rng, resident, 40, 10, 5)))
    many = kops.find_many_kernel(states, cfg, keysets, variant=variant,
                                 interpret=True)
    assert len(many) == 3
    for t, (state, k) in enumerate(zip(states, keysets)):
        solo = kops.find_fused_kernel(state, cfg, k, variant=variant,
                                      interpret=True)
        for f in solo._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(many[t], f)),
                np.asarray(getattr(solo, f)),
                err_msg=f"{variant} table {t} {f}")
        _assert_fused_equal(many[t], state, cfg, k, f"{variant} many[{t}]")


def test_find_many_is_one_launch(monkeypatch):
    rng = np.random.default_rng(31)
    cfg = table.HKVConfig(capacity=2 * 128, dim=4)
    states, keysets = [], []
    for _ in range(4):
        state, resident = _filled_table(rng, cfg, 150)
        states.append(state)
        keysets.append(u64.from_uint64(resident[:32]))
    counts = {"find_scan": 0}
    orig = _fs.find_scan

    def counting(*a, **kw):
        counts["find_scan"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(_fs, "find_scan", counting)
    kops.find_many_kernel(states, cfg, keysets, interpret=True)
    assert counts["find_scan"] == 1  # 4 tables, ONE launch


def test_find_many_validation():
    cfg = table.HKVConfig(capacity=2 * 128, dim=4)
    cfg_h = table.HKVConfig(capacity=2 * 128, dim=4, value_tier="hmem")
    k = u64.from_uint64(np.asarray([1], np.uint64))
    assert kops.find_many_kernel([], cfg, []) == []
    with pytest.raises(ValueError, match="hbm"):
        kops.find_many_kernel([table.create(cfg_h)], cfg_h, [k])
    other = table.create(table.HKVConfig(capacity=4 * 128, dim=4))
    with pytest.raises(ValueError, match="geometry"):
        kops.find_many_kernel([table.create(cfg), other], cfg, [k, k])
