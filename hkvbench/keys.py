"""The seeded key universe every cell draws from.

Rank r (0 = most popular) names the token `token(r)`, an affine bijection
of [0, 2^31); the table key of a token is (hi 0, lo token), as
`HKVEmbedding.keys_of` makes it.  The row stored for a token is a vector
of 16-bit patterns scaled into [-1, 1), exact in float32 (`rows_of`).
Both follow the generators in `chip_smoke.py`, with the seed's offset
traced so that one compiled program serves every seed, and rows centred
so that a training step moves them by more than their rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_MASK = 0x7FFFFFFF
MULT = 0x9E3779B1          # odd: r -> r * MULT + c is a bijection mod 2^31
ROW_SALT = 0x9E37
ROW_SCALE = 1.0 / 32768


def offset(seed: int) -> int:
    """The additive constant of the seed's bijection, in [0, 2^31)."""
    return (int(seed) * 0x85EBCA77) & TOKEN_MASK


def token(rank, c):
    """uint32 ranks -> int32 tokens (traced; `c` a uint32 scalar)."""
    r = jnp.asarray(rank, jnp.uint32)
    t = (r * jnp.uint32(MULT) + jnp.asarray(c, jnp.uint32))
    return (t & jnp.uint32(TOKEN_MASK)).astype(jnp.int32)


def row_values(t, col):
    """Column `col` of token `t`'s row (uint32 arrays that broadcast): a
    16-bit pattern as a multiple of 2^-15 in [-1, 1), exact in float32."""
    bits = (t ^ (col * jnp.uint32(ROW_SALT))) & jnp.uint32(0xFFFF)
    return (bits.astype(jnp.int32) - 32768).astype(jnp.float32) * ROW_SCALE


def rows_of(tok, dim: int):
    """The row written for each token: [n, dim] float32."""
    t = tok.astype(jnp.uint32)[:, None]
    return row_values(t, jnp.arange(dim, dtype=jnp.uint32)[None, :])


def prng_key(seed: int, stream: int) -> jax.Array:
    """A threefry key from a seed of any size and a stream number, as
    traced data (one compiled program for every seed)."""
    s = int(seed)
    hi = ((s >> 32) ^ (stream * 0x9E3779B9)) & 0xFFFFFFFF
    data = np.array([hi, s & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data), impl="threefry2x32")


def zipf_ranks(k, n: int, alpha: float, m: int):
    """n zipf(alpha) popularity ranks over [0, m) (traced; `k` a PRNG key),
    by the inverse CDF of the continuous power law.

    A float32 uniform resolves only 2^-23, which far out in the tail leaves
    hundreds of ranks between the ranks of two neighbouring draws; a second
    uniform spreads each draw evenly over its cell, so that every rank can
    be drawn and tail draws stay distinct."""
    if alpha == 1.0:
        raise ValueError("zipf_ranks needs alpha != 1")
    e = 1.0 - alpha
    top = float(m + 1) ** e
    ulp = jnp.float32(2.0 ** -23)
    bits = jax.random.bits(k, (2, n), jnp.uint32)
    u = (bits[0] >> 9).astype(jnp.float32) * ulp
    v = 1.0 + u * jnp.float32(top - 1.0)
    x = v ** jnp.float32(1.0 / e)                   # in [1, m + 1)
    cell = x * (jnp.float32((top - 1.0) / e) / v) * ulp   # dx/du * ulp
    spread = (bits[1] >> 8).astype(jnp.float32) * (ulp / 2) * cell
    whole = jnp.floor(x)
    r = (whole.astype(jnp.uint32)
         + jnp.floor(x - whole + spread).astype(jnp.uint32))
    return jnp.clip(r, 1, m) - jnp.uint32(1)
