"""Pallas TPU kernel: predicated bucket sweep (DESIGN.md §Maintenance).

The maintenance subsystem's bulk ops (`erase_if` / `evict_if`) start from
one whole-table pass: evaluate a `SweepPredicate` against every slot's
key/score metadata and report the per-slot match mask plus per-bucket
match counts.  On GPU the upstream library runs this as a grid-stride
kernel over buckets; on TPU it is a tiled VMEM scan exactly like the
score reduction in ``score_scan`` — each grid step streams a tile of
bucket rows (4 uint32 planes) through the VPU and emits the mask.

Fusion: liveness (EMPTY-sentinel test), the predicate compare, and the
per-bucket count reduction all happen in the single row fetch — the
metadata planes cross HBM->VMEM once per sweep, not once per stage.

Bit-parity contract: the predicate math is `core.predicates.match_planes`
— the SAME formula the pure-jnp reference path evaluates — so the kernel
and reference masks are bit-identical by construction, and everything
downstream of the mask (the coldest-first rank sort, the erase scatters)
is shared orchestration in `core/ops.py` (the `UpsertStages` pattern,
DESIGN.md §4).  Pinned in tests/test_sweep_kernel.py by full-state drains
after randomized sweeps on both backends.

Threshold operands arrive as four (1, 1) uint32 arrays mapped to every
grid step (scalar broadcast), so one compiled kernel serves every
threshold value of a given predicate kind.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.predicates import match_planes
from repro.core.u64 import empty_lanes


def _sweep_kernel(kind, kh_ref, kl_ref, sh_ref, sl_ref,
                  ah_ref, al_ref, bh_ref, bl_ref, match_ref, cnt_ref):
    kh = kh_ref[...]
    kl = kl_ref[...]
    live = ~empty_lanes(kh, kl)
    m = live & match_planes(
        kind, kh, kl, sh_ref[...], sl_ref[...],
        ah_ref[0, 0], al_ref[0, 0], bh_ref[0, 0], bl_ref[0, 0],
    )
    match_ref[...] = m.astype(jnp.int32)
    cnt_ref[:, 0] = jnp.sum(m.astype(jnp.int32), axis=1)


@functools.partial(jax.jit,
                   static_argnames=("kind", "bucket_tile", "interpret"))
def sweep_match(tkey_hi, tkey_lo, score_hi, score_lo,
                a_hi, a_lo, b_hi, b_lo, *, kind: str,
                bucket_tile: int = 8, interpret: bool):
    """Whole-table predicate evaluation.

    Returns (match bool [B, S], per-bucket count int32 [B]); `match` is
    live-entry-gated (EMPTY slots never match).  `bucket_tile=8` keeps
    each block at the natural (8, 128) vreg shape.
    """
    b, s = tkey_hi.shape
    if b % bucket_tile:
        bucket_tile = 1
    grid = (b // bucket_tile,)
    in_spec = pl.BlockSpec((bucket_tile, s), lambda i: (i, 0))
    op_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    as11 = lambda x: jnp.asarray(x, jnp.uint32).reshape(1, 1)
    match, cnt = pl.pallas_call(
        functools.partial(_sweep_kernel, kind),
        grid=grid,
        in_specs=[in_spec] * 4 + [op_spec] * 4,
        out_specs=[pl.BlockSpec((bucket_tile, s), lambda i: (i, 0)),
                   pl.BlockSpec((bucket_tile, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((b, s), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="hkv_sweep_match",
    )(tkey_hi, tkey_lo, score_hi, score_lo,
      as11(a_hi), as11(a_lo), as11(b_hi), as11(b_lo))
    return match.astype(bool), cnt[:, 0]
