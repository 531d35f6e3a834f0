"""Pallas kernel validation: sweep shapes/dtypes, assert against ref.py.

Kernels execute with interpret=True (Python on CPU) — the body semantics
are identical to a Mosaic compile on real TPUs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ops as core_ops
from repro.core import table, u64
from repro.kernels import digest_scan, gather, ref, scatter, score_scan
from repro.kernels import ops as kops


def _build_table(rng, capacity, dim, fill, dual=False, policy="lru"):
    cfg = table.HKVConfig(
        capacity=capacity, dim=dim, buckets_per_key=2 if dual else 1,
        score_policy=policy,
    )
    state = table.create(cfg)
    n = int(capacity * fill)
    if n:
        keys = rng.integers(0, 2**50, size=n).astype(np.uint64)
        vals = rng.normal(size=(n, dim)).astype(np.float32)
        state = core_ops.insert_or_assign(
            state, cfg, u64.from_uint64(keys), jnp.asarray(vals)
        ).state
    return cfg, state


@pytest.mark.parametrize("capacity,queries", [(2 * 128, 64), (8 * 128, 128), (16 * 128, 300)])
@pytest.mark.parametrize("fill", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("variant", ["tlp", "pipeline"])
def test_digest_scan_matches_ref(capacity, queries, fill, variant):
    rng = np.random.default_rng(capacity + queries + int(fill * 10))
    cfg, state = _build_table(rng, capacity, 4, fill)
    # half the queries are present keys, half are misses
    present = rng.integers(0, 2**50, size=queries).astype(np.uint64)
    qk = u64.from_uint64(present)
    from repro.core import find as find_mod

    probe = find_mod.probe_keys(cfg, qk)
    fn = lambda *a, **k: digest_scan.digest_scan(*a, variant=variant,
                                                 q_tile=32, **k)
    npad = -(-queries // 32) * 32
    pad = lambda x, f=0: jnp.concatenate(
        [x, jnp.full((npad - queries,), f, x.dtype)]
    ) if npad != queries else x
    slot_k, found_k = fn(
        state.digests, state.key_hi, state.key_lo,
        pad(probe.bucket1), pad(probe.digest.astype(jnp.uint32)),
        pad(qk.hi, u64.EMPTY_HI), pad(qk.lo, u64.EMPTY_LO),
        interpret=True,
    )
    slot_r, found_r = ref.digest_scan_ref(
        state.digests, state.key_hi, state.key_lo,
        probe.bucket1, probe.digest.astype(jnp.uint32), qk.hi, qk.lo,
    )
    np.testing.assert_array_equal(np.asarray(found_k)[:queries], np.asarray(found_r))
    fmask = np.asarray(found_r).astype(bool)
    np.testing.assert_array_equal(
        np.asarray(slot_k)[:queries][fmask], np.asarray(slot_r)[fmask]
    )


def test_locate_kernel_matches_core_locate():
    from repro.core import find as find_mod

    for dual in (False, True):
        rng = np.random.default_rng(7 + dual)
        cfg, state = _build_table(rng, 8 * 128, 4, 1.0, dual=dual)
        keys = u64.from_uint64(rng.integers(0, 2**50, size=256).astype(np.uint64))
        lk = kops.locate_kernel(state, cfg, keys, interpret=True)
        lr = find_mod.locate(state, cfg, keys)
        np.testing.assert_array_equal(np.asarray(lk.found), np.asarray(lr.found))
        m = np.asarray(lr.found)
        np.testing.assert_array_equal(np.asarray(lk.row)[m], np.asarray(lr.row)[m])


def _plane(logical):
    """Pack logical rows [N, W] into a packed value plane."""
    n, w = logical.shape
    zero = jnp.zeros(table.value_plane_shape(n, w), logical.dtype)
    return table.tier_scatter("hbm", zero, jnp.arange(n, dtype=jnp.int32),
                              logical)


@pytest.mark.parametrize("dim", [4, 32, 128, 200])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows_matches_ref(dim, dtype):
    rng = np.random.default_rng(dim)
    logical = jnp.asarray(rng.normal(size=(512, dim)), dtype)
    values = _plane(logical)
    rows = jnp.asarray(rng.integers(0, 512, size=100), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, size=100), jnp.int32)
    got = gather.gather_rows(values, rows, mask, width=dim, q_tile=4,
                             interpret=True)
    want = ref.gather_rows_ref(values, rows, mask, dim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(got),
        np.where(np.asarray(mask)[:, None] != 0,
                 np.asarray(logical)[np.asarray(rows)], 0))


@pytest.mark.parametrize("dim", [8, 64, 256])
@pytest.mark.parametrize("add", [False, True])
def test_scatter_rows_matches_ref(dim, add):
    rng = np.random.default_rng(dim + add)
    values = _plane(jnp.asarray(rng.normal(size=(256, dim)), jnp.float32))
    rows = jnp.asarray(rng.permutation(256)[:64], jnp.int32)  # unique rows
    updates = jnp.asarray(rng.normal(size=(64, dim)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, size=64), jnp.int32)
    got = scatter.scatter_rows(values, rows, updates, mask, add=add,
                               q_tile=8, interpret=True)
    want = ref.scatter_rows_ref(values, rows, updates, mask, add=add,
                                num_rows=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("buckets,fill", [(8, 0.0), (16, 0.6), (32, 1.0)])
def test_bucket_stats_matches_ref(buckets, fill):
    rng = np.random.default_rng(buckets)
    cfg, state = _build_table(rng, buckets * 128, 2, fill)
    got = score_scan.bucket_stats(
        state.key_hi, state.key_lo, state.score_hi, state.score_lo,
        bucket_tile=8, interpret=True,
    )
    want = ref.bucket_stats_ref(
        state.key_hi, state.key_lo, state.score_hi, state.score_lo
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_find_kernel_end_to_end_matches_core_find():
    rng = np.random.default_rng(11)
    cfg, state = _build_table(rng, 8 * 128, 16, 0.9)
    hits = rng.integers(0, 2**50, size=128).astype(np.uint64)
    keys = u64.from_uint64(hits)
    vals_k, found_k = kops.find_kernel(state, cfg, keys, interpret=True)
    res_c = core_ops.find(state, cfg, keys)
    np.testing.assert_array_equal(np.asarray(found_k), np.asarray(res_c.found))
    np.testing.assert_allclose(
        np.asarray(vals_k), np.asarray(res_c.values), rtol=1e-6
    )


def test_assign_kernel_matches_core_assign():
    rng = np.random.default_rng(13)
    cfg, state = _build_table(rng, 4 * 128, 8, 0.0)
    keys_np = rng.permutation(10_000)[:128].astype(np.uint64)  # unique
    keys = u64.from_uint64(keys_np)
    state = core_ops.insert_or_assign(state, cfg, keys, jnp.zeros((128, 8))).state
    upd = jnp.asarray(rng.normal(size=(128, 8)), jnp.float32)
    got = kops.assign_kernel(state, cfg, keys, upd, add=False, interpret=True)
    want = core_ops.assign(state, cfg, keys, upd)
    np.testing.assert_allclose(np.asarray(got.values), np.asarray(want.values), rtol=1e-6)
    got2 = kops.assign_kernel(state, cfg, keys, upd, add=True, interpret=True)
    want2 = core_ops.assign_add(state, cfg, keys, upd)
    np.testing.assert_allclose(np.asarray(got2.values), np.asarray(want2.values), rtol=1e-6)
