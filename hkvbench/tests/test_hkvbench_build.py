"""A tiny table from `build.py` is one the program reads as full and
exact: every placed key is found with its row on both backends, keys
never placed miss, the table is at capacity, and inserting new keys
resolves every one by eviction or rejection."""

import jax.numpy as jnp
import numpy as np
import pytest

from hkvbench import build, keys as keymod, reference
from repro.core import HKVTable, U64
from repro.core.merge import STATUS_EVICTED, STATUS_REJECTED
from repro.embedding import HKVEmbedding

SEED = 3_000_000_019


@pytest.fixture(scope="module")
def table():
    cfg = HKVEmbedding(capacity=8 * 128, dim=8).config()
    return HKVTable.wrap(build.build(cfg, SEED), cfg, "jnp")


def _keys(tok):
    tok = jnp.asarray(tok, jnp.uint32)
    return U64(jnp.zeros_like(tok), tok)


def _placed(table):
    lo = np.asarray(table.state.key_lo).reshape(-1)
    return lo[np.asarray(table.state.key_hi).reshape(-1) == 0]


def test_size_is_capacity(table):
    assert int(table.size()) == table.capacity
    assert len(np.unique(_placed(table))) == table.capacity


@pytest.mark.parametrize("backend", ["jnp", "kernel"])
def test_every_placed_key_found_with_its_row(table, backend):
    tok = _placed(table)[:256]
    r = table.with_backend(backend).find(_keys(tok))
    assert np.asarray(r.found).all()
    np.testing.assert_array_equal(np.asarray(r.values),
                                  reference.rows_np(tok, table.dim))


def test_placement_follows_the_reference_rule(table):
    tok = _placed(table)
    c = reference.offset(SEED)
    slot = reference.resident_slot(
        reference.rank_np(tok, c), c, table.cfg.num_buckets,
        table.cfg.slots_per_bucket, True, build.ranks_drawn(table.cfg))
    assert (slot >= 0).all()
    plane = reference.loaded_rank_plane(
        c, table.cfg.num_buckets, table.cfg.slots_per_bucket, True,
        build.ranks_drawn(table.cfg))
    want = np.where(plane >= 0, reference.token_np(np.maximum(plane, 0), c),
                    -1)
    np.testing.assert_array_equal(
        np.asarray(table.state.key_lo).astype(np.int64), want)


def test_unplaced_keys_miss(table):
    c = reference.offset(SEED)
    n = build.ranks_drawn(table.cfg)
    tok = reference.token_np(np.arange(n, n + 256), c)
    assert not np.asarray(table.find(_keys(tok)).found).any()
    drawn = reference.token_np(np.arange(n), c)
    left_out = np.setdiff1d(drawn, _placed(table))[:256]
    assert len(left_out) and not np.asarray(
        table.find(_keys(left_out)).found).any()


def test_new_keys_resolve_by_eviction_or_rejection(table):
    c = reference.offset(SEED)
    n = build.ranks_drawn(table.cfg)
    tok = reference.token_np(np.arange(n, n + 512), c)
    r = table.insert_or_assign(_keys(tok),
                               keymod.rows_of(jnp.asarray(tok), table.dim))
    st = np.asarray(r.status)
    assert np.isin(st, (STATUS_EVICTED, STATUS_REJECTED)).all()
    assert (st == STATUS_EVICTED).any()
    assert int(r.table.size()) == table.capacity

