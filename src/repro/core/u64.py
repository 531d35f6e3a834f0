"""64-bit keys/scores as (hi, lo) uint32 pairs — the TPU-native representation.

The paper stores uint64 keys and uint64 scores. TPU VPU lanes are 32-bit and
JAX defaults to 32-bit integers, so we carry every 64-bit quantity as a pair
of uint32 planes (hi, lo).  All comparisons are lexicographic on (hi, lo),
which induces exactly the unsigned-uint64 total order, so score policies and
sentinel reservation behave identically to the paper's uint64 semantics.

The hash is a TPU adaptation of the paper's "GPU-optimized hash derived from
Murmur3": two coupled Murmur3 fmix32 finalizer passes yield two independent
32-bit hashes per key — h1 drives the primary bucket + the 8-bit digest,
h2 drives the secondary bucket (dual-bucket mode).  See DESIGN.md §2.
"""

from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import roles

UINT32_MASK = np.uint64(0xFFFFFFFF)

# Reserved sentinel: the all-ones key marks an empty slot (the paper reserves
# EMPTY and LOCKED sentinels; the batch-synchronous TPU design needs no LOCKED).
EMPTY_HI = np.uint32(0xFFFFFFFF)
EMPTY_LO = np.uint32(0xFFFFFFFF)
# The same sentinel as one host-side uint64 (the padding value callers put
# in raw numpy key arrays) — the ONE definition every layer imports.
EMPTY_KEY = np.uint64(0xFFFFFFFFFFFFFFFF)
# Digest stored in empty slots. Any value is *correct* (key compare resolves
# false positives); 0xFF is reserved-looking and aids debugging.
EMPTY_DIGEST = np.uint8(0xFF)


class U64(NamedTuple):
    """A batch of 64-bit unsigned integers as two uint32 planes."""

    hi: jax.Array
    lo: jax.Array

    @property
    def shape(self):
        return self.hi.shape

    def __getitem__(self, idx):  # type: ignore[override]
        return U64(self.hi[idx], self.lo[idx])

    def reshape(self, *shape):
        return U64(self.hi.reshape(*shape), self.lo.reshape(*shape))


# ---------------------------------------------------------------------------
# Construction / conversion
# ---------------------------------------------------------------------------

def from_uint64(x: Union[np.ndarray, int]) -> U64:
    """Host-side conversion from numpy uint64 (or python int) to U64."""
    arr = np.asarray(x, dtype=np.uint64)
    hi = (arr >> np.uint64(32)).astype(np.uint32)
    lo = (arr & UINT32_MASK).astype(np.uint32)
    return U64(jnp.asarray(hi), jnp.asarray(lo))


def to_uint64(x: U64) -> np.ndarray:
    """Host-side conversion back to numpy uint64."""
    hi = np.asarray(jax.device_get(x.hi)).astype(np.uint64)
    lo = np.asarray(jax.device_get(x.lo)).astype(np.uint64)
    return (hi << np.uint64(32)) | lo


def make(hi, lo) -> U64:
    return U64(jnp.asarray(hi, jnp.uint32), jnp.asarray(lo, jnp.uint32))


def full(shape, value: int) -> U64:
    v = int(value)
    return U64(
        jnp.full(shape, np.uint32((v >> 32) & 0xFFFFFFFF), jnp.uint32),
        jnp.full(shape, np.uint32(v & 0xFFFFFFFF), jnp.uint32),
    )


def zeros(shape) -> U64:
    return U64(jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.uint32))


def empty_sentinel(shape) -> U64:
    return U64(jnp.full(shape, EMPTY_HI, jnp.uint32), jnp.full(shape, EMPTY_LO, jnp.uint32))


# ---------------------------------------------------------------------------
# Comparison (lexicographic == unsigned 64-bit order)
# ---------------------------------------------------------------------------

def eq(a: U64, b: U64) -> jax.Array:
    return (a.hi == b.hi) & (a.lo == b.lo)


def ne(a: U64, b: U64) -> jax.Array:
    return (a.hi != b.hi) | (a.lo != b.lo)


def lt(a: U64, b: U64) -> jax.Array:
    return (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo < b.lo))


def le(a: U64, b: U64) -> jax.Array:
    return (a.hi < b.hi) | ((a.hi == b.hi) & (a.lo <= b.lo))


def gt(a: U64, b: U64) -> jax.Array:
    return lt(b, a)


def ge(a: U64, b: U64) -> jax.Array:
    return le(b, a)


def select(pred: jax.Array, a: U64, b: U64) -> U64:
    return U64(jnp.where(pred, a.hi, b.hi), jnp.where(pred, a.lo, b.lo))


def minimum(a: U64, b: U64) -> U64:
    return select(le(a, b), a, b)


def maximum(a: U64, b: U64) -> U64:
    return select(ge(a, b), a, b)


def empty_lanes(hi, lo) -> jax.Array:
    """Plane-level EMPTY-sentinel test — the one liveness formula.

    Takes raw (hi, lo) uint32 planes rather than a U64 so the same body
    serves jnp table planes and VMEM rows inside Pallas kernel bodies
    (occupancy masks, sweep liveness).  Kernels must call this instead of
    re-deriving the all-ones compare inline — hkv-lint's oracle-coupling
    checker flags inline forks.
    """
    return (hi == EMPTY_HI) & (lo == EMPTY_LO)


def is_empty(a: U64) -> jax.Array:
    return empty_lanes(a.hi, a.lo)


# ---------------------------------------------------------------------------
# Arithmetic (used by score policies)
# ---------------------------------------------------------------------------

def add_u32(a: U64, inc) -> U64:
    """a + inc, where inc is uint32 (broadcastable). Carries into hi."""
    inc = jnp.asarray(inc, jnp.uint32)
    lo = a.lo + inc
    carry = (lo < a.lo).astype(jnp.uint32)  # wrapped => carry
    return U64(a.hi + carry, lo)


def add(a: U64, b: U64) -> U64:
    lo = a.lo + b.lo
    carry = (lo < a.lo).astype(jnp.uint32)
    return U64(a.hi + b.hi + carry, lo)


# ---------------------------------------------------------------------------
# Multi-key sorting.
#
# A u64 order needs two 32-bit sort keys (hi major, lo minor), and the batch
# closure orders by up to six.  The TPU compiler builds one `lax.sort` with k
# keys and m operands in time that grows steeply with both (a 5-key,
# 11-operand sort of 2^16 elements takes minutes), so orders are built as
# one stable single-key sort of (key, permutation) per key, least
# significant first, and payloads are gathered through the permutation.
# ---------------------------------------------------------------------------

@roles.scoped("hkv.closure")
def lexsort(keys, axis: int = 0) -> jax.Array:
    """int32 permutation ordering `keys` (most significant first)
    lexicographically along `axis`; ties keep their index order, as a
    stable `lax.sort` of (*keys, iota) would."""
    axis = axis % keys[0].ndim
    perm = None
    for k in reversed(keys):
        if perm is None:
            iota = jax.lax.broadcasted_iota(jnp.int32, k.shape, axis)
            _, perm = jax.lax.sort((k, iota), dimension=axis, num_keys=1,
                                   is_stable=True)
        else:
            kp = jnp.take_along_axis(k, perm, axis=axis)
            _, perm = jax.lax.sort((kp, perm), dimension=axis, num_keys=1,
                                   is_stable=True)
    return perm


# ---------------------------------------------------------------------------
# Hashing: Murmur3 fmix32-derived hash pair (TPU adaptation, DESIGN.md §2)
# ---------------------------------------------------------------------------

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
_SALT2 = np.uint32(0x7FEB352D)


def fmix32(h: jax.Array) -> jax.Array:
    """Murmur3 32-bit finalizer (avalanche) — pure uint32 ops."""
    h = jnp.asarray(h, jnp.uint32)
    h = h ^ (h >> 16)
    h = h * _C1
    h = h ^ (h >> 13)
    h = h * _C2
    h = h ^ (h >> 16)
    return h


def hash_pair(key: U64) -> tuple[jax.Array, jax.Array]:
    """Two decorrelated 32-bit hashes of a 64-bit key.

    h1 -> primary bucket + digest, h2 -> secondary bucket.  Both mix *all*
    64 input bits (hi feeds lo's pass and vice versa), so single-plane key
    patterns (e.g. sequential lows) still avalanche fully.
    """
    a = fmix32(key.hi ^ _GOLDEN)
    b = fmix32(key.lo ^ _SALT2)
    h1 = fmix32(a ^ key.lo)
    h2 = fmix32(b ^ key.hi ^ _GOLDEN)
    return h1, h2


def digest_from_hash(h1: jax.Array) -> jax.Array:
    """8-bit digest from the top byte of h1 (paper: bits [31:24] of the hash).

    Bucket selection uses the *low* bits of h1 (mod num_buckets), so digest
    and bucket index are decorrelated, as in the paper.
    """
    return ((h1 >> 24) & np.uint32(0xFF)).astype(jnp.uint8)


def bucket_from_hash(h: jax.Array, num_buckets: int) -> jax.Array:
    nb = np.uint32(num_buckets)
    if num_buckets & (num_buckets - 1) == 0:
        return (h & (nb - np.uint32(1))).astype(jnp.int32)
    return (h % nb).astype(jnp.int32)


# Reference (host/numpy) implementations for property tests -----------------

def fmix32_np(h: np.ndarray) -> np.ndarray:
    h = np.asarray(h, np.uint32).copy()
    with np.errstate(over="ignore"):
        h ^= h >> np.uint32(16)
        h = (h.astype(np.uint64) * np.uint64(0x85EBCA6B) & UINT32_MASK).astype(np.uint32)
        h ^= h >> np.uint32(13)
        h = (h.astype(np.uint64) * np.uint64(0xC2B2AE35) & UINT32_MASK).astype(np.uint32)
        h ^= h >> np.uint32(16)
    return h


def hash_pair_np(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    keys = np.asarray(keys, np.uint64)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    lo = (keys & UINT32_MASK).astype(np.uint32)
    a = fmix32_np(hi ^ np.uint32(_GOLDEN))
    b = fmix32_np(lo ^ np.uint32(_SALT2))
    h1 = fmix32_np(a ^ lo)
    h2 = fmix32_np(b ^ hi ^ np.uint32(_GOLDEN))
    return h1, h2
