"""In-kernel bucket-row access shared by the per-query probe kernels.

Every probe kernel (``digest_scan``, ``find_scan``, ``update_scan``,
``upsert_scan``) walks a tile of queries and, per query, copies whole
bucket rows of the ``[B, 128]`` metadata planes from HBM into VMEM by
manual DMA — the row index is a scalar-prefetched bucket id, so no
BlockSpec can express it.  What the TPU's DMA engine accepts shapes the
helpers here:

* ``uint32`` planes (keys, scores): one 128-lane row per copy.
* the ``uint8`` digest plane: its HBM tiling packs rows, so a copy must
  start at, and span, an aligned group of ``digest_group(B)`` rows; the
  query's row is selected from the group in-kernel.
* per-query scalars travel in SMEM (scalar prefetch in, SMEM out), and
  vector results reduce to scalars through ``int32`` — the chip's
  compiler reduces no unsigned integers, so ``uint32`` score order goes
  through ``ordered`` (sign-bit flip, which keeps unsigned order exactly).

``depth`` selects the two kernel variants: 1 fetches and waits per query
(``tlp``), 2 streams query q+1's rows while query q is compared
(``pipeline``, a two-slot double buffer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128            # TPU vreg minor dimension == slots per bucket
DIGEST_GROUP = 8       # rows per aligned uint8 digest-plane copy
_DEPTH = {"tlp": 1, "pipeline": 2}


def variant_depth(variant: str) -> int:
    """Bucket-row buffers of a kernel variant (see `query_loop`)."""
    if variant not in _DEPTH:
        raise ValueError(f"unknown variant {variant!r}")
    return _DEPTH[variant]


def digest_group(num_buckets: int) -> int:
    """Rows per digest copy: an aligned group where the plane allows one
    (every real table: B is a multiple of 8), else single rows (tiny
    tables, which only the interpreter runs)."""
    return DIGEST_GROUP if num_buckets % DIGEST_GROUP == 0 else 1


def lane_iota(lanes: int = LANES) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)


def first_lane(m: jax.Array):
    """(any, index of the first set lane or 0) of a bool [1, 128] mask —
    `jnp.any` / `jnp.argmax` through an int32 min."""
    idx = jnp.min(jnp.where(m, lane_iota(m.shape[-1]), m.shape[-1]))
    hit = idx < m.shape[-1]
    return hit, jnp.where(hit, idx, 0)


def as_i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def pick(row: jax.Array, lane_mask: jax.Array) -> jax.Array:
    """The int32 bits of `row` at the one lane `lane_mask` selects."""
    return jnp.sum(jnp.where(lane_mask, as_i32(row), 0))


def ordered(x: jax.Array) -> jax.Array:
    """uint32 -> int32 with the same order (sign bit flipped)."""
    return as_i32(x ^ jnp.uint32(0x80000000))


def digest_row(group: jax.Array, b) -> jax.Array:
    """Bucket b's digests [1, 128] (uint32) out of its fetched row group."""
    g = group.shape[0]
    if g == 1:
        return group.astype(jnp.uint32)
    sub = jax.lax.broadcasted_iota(jnp.int32, group.shape, 0)
    rows = jnp.where(sub == b % g, group.astype(jnp.int32), 0)
    return jnp.sum(rows, axis=0, keepdims=True).astype(jnp.uint32)


def row_copy(plane, buf, sem, b, group: int = 1):
    """DMA descriptor: plane rows of bucket b (its aligned group) -> buf."""
    start = b if group == 1 else pl.multiple_of((b // group) * group, group)
    return pltpu.make_async_copy(plane.at[pl.ds(start, group), :], buf, sem)


def query_loop(q_tile: int, depth: int, copies, body) -> None:
    """Run `body(q, slot)` for the tile's queries with their bucket rows
    resident in buffer `slot`; `copies(q, slot)` lists query q's DMAs.

    depth 1: fetch, wait, compare, per query.  depth 2: query q+1's
    rows stream into the other slot while query q is compared.
    """
    if depth == 1:
        def step(q, carry):
            cps = copies(q, 0)
            for c in cps:
                c.start()
            for c in cps:
                c.wait()
            body(q, 0)
            return carry
    else:
        for c in copies(0, 0):
            c.start()

        def step(q, carry):
            cur = jax.lax.rem(q, 2)
            nxt = 1 - cur

            @pl.when(q + 1 < q_tile)
            def _():
                for c in copies(q + 1, nxt):
                    c.start()

            for c in copies(q, cur):
                c.wait()
            body(q, cur)
            return carry

    jax.lax.fori_loop(0, q_tile, step, 0)


def smem_out(n: int):
    """A per-query int32 [n] output, resident in SMEM for the whole call."""
    return (pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            jax.ShapeDtypeStruct((n,), jnp.int32))


def hbm():
    """A whole plane left in HBM, for the kernel's own DMAs."""
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
