"""Digest-accelerated lookup (paper §3.2, Algorithm 1).

Pure-jnp reference path.  The perf-critical variant lives in
``repro.kernels.digest_scan`` (Pallas, scalar-prefetched bucket rows); both
implement exactly this contract and are cross-checked in tests.

The lookup contract is the paper's Proposition 3.1 adapted to batch form:
for every query key, gather its candidate bucket row(s), compare the 8-bit
digests in one vectorized pass (the TPU analogue of the single 128 B
cache-line transaction: 128 digests = one VPU lane row), and compare full
keys only where digests match.  A miss is definitive after one bucket row
(single-bucket mode) or two rows (dual-bucket mode).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import u64
from repro.core.table import HKVConfig, HKVState
from repro.core.u64 import U64


class Probe(NamedTuple):
    """Hash-derived routing info for a batch of keys."""

    bucket1: jax.Array   # int32 [N] primary bucket
    bucket2: jax.Array   # int32 [N] secondary bucket (== bucket1 in single mode)
    digest: jax.Array    # uint8 [N]
    valid: jax.Array     # bool  [N] — key is not the EMPTY sentinel


class Locate(NamedTuple):
    """Result of locating keys in the table (key-side only, no value touch)."""

    found: jax.Array     # bool  [N]
    bucket: jax.Array    # int32 [N] bucket holding the key (b1 if miss)
    slot: jax.Array      # int32 [N] slot holding the key (0 if miss)
    row: jax.Array       # int32 [N] value row = bucket * S + slot (position addressing)


def probe_keys(cfg: HKVConfig, keys: U64) -> Probe:
    h1, h2 = u64.hash_pair(keys)
    b1 = u64.bucket_from_hash(h1, cfg.num_buckets)
    if cfg.buckets_per_key == 2:
        b2 = u64.bucket_from_hash(h2, cfg.num_buckets)
    else:
        b2 = b1
    return Probe(
        bucket1=b1,
        bucket2=b2,
        digest=u64.digest_from_hash(h1),
        valid=~u64.is_empty(keys),
    )


def match_lanes(key_hi, key_lo, q_hi, q_lo, digests=None, q_digest=None):
    """THE key-match formula (paper §3.2, Algorithm 1 lines 4–10).

    Pure plane math — uint32/uint8 lane compares only, no gathers and no
    dtype casts — so the identical function body runs under jnp on
    ``[N, S]`` bucket rows *and* inside Pallas kernel bodies on ``[S]``
    (or ``[T, S]``) VMEM rows.  This is the single definition every probe
    stage must call: the jnp reference (via :func:`_match_in_bucket`) and
    the ``digest_scan`` / ``find_scan`` / ``upsert_scan`` kernels.  hkv-lint's
    oracle-coupling checker (``repro.analysis.oracle_coupling``) fails the
    build if a kernel re-derives this conjunction inline, so the kernel and
    reference paths cannot silently fork.

    When ``digests``/``q_digest`` are given the 8-bit digest pre-filter is
    folded into the mask (~1/256 false-positive rate, resolved by the full
    key compare in the same expression).  Callers pass them pre-broadcast
    and pre-cast: the formula itself never changes dtypes.
    """
    m = (key_hi == q_hi) & (key_lo == q_lo)
    if digests is not None:
        m = m & (digests == q_digest)
    return m


def _match_in_bucket(
    state: HKVState, bucket: jax.Array, keys: U64, digest: jax.Array,
    use_digest: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(hit[N], slot[N]) of `keys` within rows `bucket`.

    Digest pre-filter first (one int8 == over the 128-lane row), then full
    64-bit key compare only on digest matches — Algorithm 1 lines 4–10.
    use_digest=False is the Exp#3a ablation: all 128 full-key compares.
    """
    khi = state.key_hi[bucket]                       # uint32 [N, S]
    klo = state.key_lo[bucket]
    if use_digest:
        kmask = match_lanes(khi, klo, keys.hi[:, None], keys.lo[:, None],
                            state.digests[bucket], digest[:, None])
    else:
        kmask = match_lanes(khi, klo, keys.hi[:, None], keys.lo[:, None])
    hit = jnp.any(kmask, axis=1)
    slot = jnp.argmax(kmask, axis=1).astype(jnp.int32)
    return hit, slot


def locate(state: HKVState, cfg: HKVConfig, keys: U64, probe: Probe | None = None) -> Locate:
    """Find which (bucket, slot) holds each key, if any.

    Invariant: a key occupies at most one slot table-wide (the upsert path
    only inserts keys it failed to locate, and always into one bucket), so
    first-match is the unique match.
    """
    if probe is None:
        probe = probe_keys(cfg, keys)
    hit1, slot1 = _match_in_bucket(state, probe.bucket1, keys, probe.digest,
                                   cfg.use_digest)
    if cfg.buckets_per_key == 2:
        hit2, slot2 = _match_in_bucket(state, probe.bucket2, keys, probe.digest,
                                       cfg.use_digest)
        found = (hit1 | hit2) & probe.valid
        bucket = jnp.where(hit1, probe.bucket1, jnp.where(hit2, probe.bucket2, probe.bucket1))
        slot = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
    else:
        found = hit1 & probe.valid
        bucket = probe.bucket1
        slot = jnp.where(hit1, slot1, 0)
    s = state.slots_per_bucket
    return Locate(found=found, bucket=bucket, slot=slot, row=bucket * s + slot)


def gather_values(state: HKVState, loc: Locate, dim: int | None,
                  cfg: HKVConfig) -> jax.Array:
    """Position-addressed value gather (paper §3.6): row = bucket*S + slot.

    Missing keys return zeros.  `dim` trims aux optimizer-state columns.
    In 'hmem' tier mode only the touched rows cross the host boundary.
    """
    from repro.core import table as table_mod

    rows = table_mod.tier_gather(cfg.value_tier, state.values, loc.row,
                                 cfg.total_value_dim)
    if dim is not None:
        rows = rows[:, :dim]
    return jnp.where(loc.found[:, None], rows, jnp.zeros_like(rows))
