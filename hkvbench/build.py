"""A full (lambda = 1.0) table made from the seed, on the device.

Filling a 2^27-slot table through the program's inserter takes minutes, so
the benchmark writes the state directly.  This is the ONE file that knows
the table's layout; it imports the program's hash and layout helpers and
copies none of them.  A change to `HKVState`'s fields, the hash, or the
packed value plane needs this file to follow.

The rule (the reference in `reference.py` restates it independently):
  * ranks 0, 1, 2, ... are taken in order (rank order is the traffic's
    popularity order: rank 0 is the most popular key);
  * rank r lands in one of its own candidate buckets: its secondary when r
    is odd and the table is dual-bucket, else its primary;
  * the first `slots_per_bucket` ranks that land in a bucket fill it, in
    ascending slot order, as the inserter fills empty slots;
  * each resident key carries its digest, the LRU score `N - r` (scores
    fall with rank; N ranks are drawn) and the row `rows_of(token)` with
    zeroed optimizer columns; the clock is set to N + 1, past every score.
N is twice the capacity: every bucket then fills (a bucket short of 128
arrivals after 2 x 128 expected is 8 standard deviations out), and the
build checks it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hkvbench import keys as keymod
from repro.core import u64
from repro.core.table import HKVConfig, HKVState, value_pitch, value_plane_shape
from repro.core.u64 import U64

CHUNK_ELEMS = 1 << 22   # ranks placed per step


def ranks_drawn(cfg: HKVConfig) -> int:
    return 2 * cfg.capacity


def landing_bucket(cfg: HKVConfig, rank, tok):
    h1, h2 = u64.hash_pair(U64(jnp.zeros_like(tok, jnp.uint32),
                               tok.astype(jnp.uint32)))
    b1 = u64.bucket_from_hash(h1, cfg.num_buckets)
    if cfg.buckets_per_key == 1:
        return b1
    b2 = u64.bucket_from_hash(h2, cfg.num_buckets)
    return jnp.where((rank & 1) == 1, b2, b1)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2, 3))
def _place(cfg: HKVConfig, k: int, rank_plane, fill, c, start):
    """Place ranks start .. start+k-1: [B, S] rank plane, [B] arrivals."""
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    rank = start + jnp.arange(k, dtype=jnp.uint32)
    land = landing_bucket(cfg, rank, keymod.token(rank, c))
    order = jnp.argsort(land, stable=True)          # ranks ascend in a run
    ls, rs = land[order], rank[order]
    iota = jnp.arange(k, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), ls[1:] != ls[:-1]])
    pos = iota - jax.lax.cummax(jnp.where(first, iota, 0))
    slot = fill[ls] + pos
    row = jnp.where(slot < s, ls, b)                # past the plane: dropped
    rank_plane = rank_plane.at[row, slot].set(rs.astype(jnp.int32),
                                              mode="drop")
    return rank_plane, fill.at[land].add(1)


@functools.partial(jax.jit, static_argnums=(0,))
def _materialize(cfg: HKVConfig, rank_plane, c, n_ranks):
    """The table state, every plane computed element-wise from the ranks."""
    width = cfg.total_value_dim
    live = rank_plane >= 0
    rank = rank_plane.astype(jnp.uint32)
    tok = keymod.token(rank, c).astype(jnp.uint32)
    h1, _ = u64.hash_pair(U64(jnp.zeros_like(tok), tok))
    empty_hi = jnp.uint32(u64.EMPTY_HI)
    score_lo = jnp.uint32(n_ranks) - rank
    rows, lanes = value_plane_shape(cfg.capacity, width)
    pitch = value_pitch(width)
    per = lanes // pitch
    lane = jax.lax.broadcasted_iota(jnp.uint32, (1, lanes), 1)
    col = lane % pitch
    tok_r = tok.reshape(rows, per)
    live_r = live.reshape(rows, per)
    slot_of_lane = lane // pitch
    t = jnp.zeros((rows, lanes), jnp.uint32)
    ok = jnp.zeros((rows, lanes), bool)
    for j in range(per):
        here = slot_of_lane == j
        t = jnp.where(here, tok_r[:, j:j + 1], t)
        ok = jnp.where(here, live_r[:, j:j + 1], ok)
    values = jnp.where(ok & (col < cfg.dim), keymod.row_values(t, col), 0.0)
    clock = jnp.uint32(n_ranks) + jnp.uint32(1)
    return HKVState(
        key_hi=jnp.where(live, jnp.uint32(0), empty_hi),
        key_lo=jnp.where(live, tok, jnp.uint32(u64.EMPTY_LO)),
        digests=jnp.where(live, u64.digest_from_hash(h1),
                          jnp.uint8(u64.EMPTY_DIGEST)),
        score_hi=jnp.zeros(rank_plane.shape, jnp.uint32),
        score_lo=jnp.where(live, score_lo, jnp.uint32(0)),
        values=values.astype(cfg.value_dtype),
        clock_hi=jnp.zeros((), jnp.uint32),
        clock_lo=clock,
        epoch=jnp.zeros((), jnp.uint32),
    ), jnp.sum(live.astype(jnp.int32))


def build(cfg: HKVConfig, seed: int) -> HKVState:
    """The lambda = 1.0 state of `cfg` for `seed` (raises if a bucket is
    left short)."""
    if cfg.value_tier != "hbm" or cfg.score_policy != "lru":
        raise ValueError("build() writes HBM tables under LRU scores")
    if cfg.dim > cfg.total_value_dim or cfg.value_dtype != jnp.float32:
        raise ValueError("build() writes float32 rows")
    n = ranks_drawn(cfg)
    k = min(n, CHUNK_ELEMS)
    c = jnp.uint32(keymod.offset(seed))
    plane = jnp.full((cfg.num_buckets, cfg.slots_per_bucket), -1, jnp.int32)
    fill = jnp.zeros((cfg.num_buckets,), jnp.int32)
    for start in range(0, n, k):
        plane, fill = _place(cfg, k, plane, fill, c, jnp.uint32(start))
    del fill
    state, size = _materialize(cfg, plane, c, jnp.uint32(n))
    del plane
    if int(size) != cfg.capacity:
        raise RuntimeError(f"build left {cfg.capacity - int(size)} slots "
                           f"empty of {cfg.capacity}")
    return state
