"""HKV table configuration + state (paper §3.1–§3.2, Fig. 4).

Memory layout mirrors the paper's bucket design, expressed as structure-of-
arrays (the natural XLA/TPU layout):

  digests : uint8  [B, S]   one contiguous 128-byte row per bucket — the
                            TPU analogue of the GPU L1 cache-line-aligned
                            digest array (one VPU lane row covers the whole
                            candidate set; see DESIGN.md §2)
  key_hi  : uint32 [B, S]   64-bit keys as two planes
  key_lo  : uint32 [B, S]
  score_hi: uint32 [B, S]   64-bit scores as two planes
  score_lo: uint32 [B, S]
  values  : vdtype [R, L]  the packed value plane (position-based
                            addressing, paper §3.6): slot (b, s) is logical
                            row g = b*S + s, stored at plane row
                            g // (L // P), lanes [(g % (L // P)) * P, +W),
                            where W = dim + aux, P = value_pitch(W) and
                            L = max(128, P) — no per-entry pointer anywhere.
                            Every plane row is lane-dense, so a kernel DMAs
                            one 128-lane row per slot (DESIGN.md §2).

`values` may live on a different memory tier than the key-side arrays
(tiered key-value separation, §3.6): `value_tier='hmem'` places the plane
in host memory (`memory_kind='pinned_host'`); key-side processing stays in
HBM either way.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental.compute_on import compute_on

from repro.core import u64
from repro.core.scores import ScorePolicy, get_policy
from repro.core.u64 import U64

SLOTS_PER_BUCKET = 128  # the paper's (and the TPU lane width's) natural choice
LANES = 128             # TPU vreg minor dimension


def value_pitch(width: int) -> int:
    """Lanes one slot's row occupies in the packed value plane: the next
    power of two up to 128 lanes (so rows never straddle a plane row),
    else the next multiple of 128."""
    if width <= LANES:
        return 1 << (width - 1).bit_length()
    return -(-width // LANES) * LANES


def value_plane_shape(num_slots: int, width: int) -> tuple[int, int]:
    pitch = value_pitch(width)
    lanes = max(LANES, pitch)
    return -(-num_slots * pitch // lanes), lanes


def value_coords(rows: jax.Array, width: int, lanes: int):
    """(plane row [N], first lane [N]) of logical value rows `rows`."""
    pitch = value_pitch(width)
    per = lanes // pitch
    return rows // per, (rows % per) * pitch


@dataclasses.dataclass(frozen=True)
class HKVConfig:
    """Static configuration of an HKV table."""

    capacity: int                      # total slots (B * S)
    dim: int                           # value vector length
    slots_per_bucket: int = SLOTS_PER_BUCKET
    buckets_per_key: int = 1           # 1 = single-bucket, 2 = dual-bucket (§3.4)
    score_policy: str = "lru"
    value_dtype: jnp.dtype = jnp.float32
    value_tier: str = "hbm"            # 'hbm' | 'hmem' (tiered KV separation §3.6)
    # Optional per-slot optimizer-state columns appended to each value row
    # (momentum etc. colocated with the embedding row, HugeCTR-style).
    aux_value_dim: int = 0
    # Ablation switch (Exp#3a): disable the 8-bit digest pre-filter so every
    # lookup compares all 128 full keys (paper Table 7's "No digest" column).
    use_digest: bool = True

    def __post_init__(self):
        if self.capacity % self.slots_per_bucket != 0:
            raise ValueError(
                f"capacity {self.capacity} must be a multiple of "
                f"slots_per_bucket {self.slots_per_bucket}"
            )
        if self.buckets_per_key not in (1, 2):
            raise ValueError("buckets_per_key must be 1 or 2")
        if self.value_tier not in ("hbm", "hmem"):
            raise ValueError("value_tier must be 'hbm' or 'hmem'")
        if self.num_buckets < 1:
            raise ValueError("capacity must hold at least one bucket")

    @property
    def num_buckets(self) -> int:
        return self.capacity // self.slots_per_bucket

    @property
    def total_value_dim(self) -> int:
        return self.dim + self.aux_value_dim

    @property
    def policy(self) -> ScorePolicy:
        return get_policy(self.score_policy)

    @property
    def value_plane_shape(self) -> tuple[int, int]:
        return value_plane_shape(self.capacity, self.total_value_dim)

    def bytes_per_entry(self) -> int:
        # key 8 B + digest 1 B + score 8 B (paper §5.1: 17 B metadata) + the
        # stored value row (its padded pitch in the packed plane)
        return 17 + (value_pitch(self.total_value_dim)
                     * jnp.dtype(self.value_dtype).itemsize)


class HKVState(NamedTuple):
    """The table as a pytree of arrays (pure-functional state)."""

    key_hi: jax.Array    # uint32 [B, S]
    key_lo: jax.Array    # uint32 [B, S]
    digests: jax.Array   # uint8  [B, S]
    score_hi: jax.Array  # uint32 [B, S]
    score_lo: jax.Array  # uint32 [B, S]
    values: jax.Array    # vdtype [R, L] packed value plane (see module doc)
    clock_hi: jax.Array  # uint32 [] — global monotonic batch clock (LRU)
    clock_lo: jax.Array  # uint32 []
    epoch: jax.Array     # uint32 [] — application epoch (epoch_* policies)

    # -- typed views ---------------------------------------------------------

    @property
    def keys(self) -> U64:
        return U64(self.key_hi, self.key_lo)

    @property
    def scores(self) -> U64:
        return U64(self.score_hi, self.score_lo)

    @property
    def clock(self) -> U64:
        return U64(self.clock_hi, self.clock_lo)

    @property
    def num_buckets(self) -> int:
        return self.key_hi.shape[0]

    @property
    def slots_per_bucket(self) -> int:
        return self.key_hi.shape[1]

    def occupied_mask(self) -> jax.Array:
        return ~u64.is_empty(self.keys)

    def load_factor(self) -> jax.Array:
        occ = jnp.sum(self.occupied_mask().astype(jnp.int32))
        return occ.astype(jnp.float32) / float(self.key_hi.size)

    def bucket_occupancy(self) -> jax.Array:
        """int32 [B] — number of live entries per bucket."""
        return jnp.sum(self.occupied_mask().astype(jnp.int32), axis=1)


def create(config: HKVConfig) -> HKVState:
    """Allocate an empty table."""
    b, s = config.num_buckets, config.slots_per_bucket
    key_hi = jnp.full((b, s), u64.EMPTY_HI, jnp.uint32)
    shape, dtype = config.value_plane_shape, config.value_dtype
    if config.value_tier != "hmem":
        values = jnp.zeros(shape, dtype)
    elif isinstance(key_hi, jax.core.Tracer):
        # built inside a trace: the plane is typed host-resident, and the
        # jit's output placement decides where it lands
        values = jax.device_put(jnp.zeros(shape, dtype), _HOST)
    else:
        values = place_on_host(np.zeros(shape, dtype))
    return HKVState(
        key_hi=key_hi,
        key_lo=jnp.full((b, s), u64.EMPTY_LO, jnp.uint32),
        digests=jnp.full((b, s), u64.EMPTY_DIGEST, jnp.uint8),
        score_hi=jnp.zeros((b, s), jnp.uint32),
        score_lo=jnp.zeros((b, s), jnp.uint32),
        values=values,
        clock_hi=jnp.zeros((), jnp.uint32),
        clock_lo=jnp.zeros((), jnp.uint32),
        epoch=jnp.zeros((), jnp.uint32),
    )


def place_on_host(values) -> jax.Array:
    """Copy a value plane (a host numpy array or a device array) into the
    default device's `pinned_host` memory — the paper's HMEM tier — without
    staging it in HBM.  Raises where the placement does not land there: a
    config asking for the host tier never silently runs as an HBM table."""
    sharding = jax.sharding.SingleDeviceSharding(
        jax.devices()[0], memory_kind="pinned_host")
    placed = jax.device_put(values, sharding)
    if placed.sharding.memory_kind != "pinned_host":
        raise RuntimeError(
            f"value plane {values.shape} was not placed in pinned_host "
            f"memory (got {placed.sharding.memory_kind!r})")
    return placed


# ---------------------------------------------------------------------------
# Value-plane row access (§3.6).  Logical rows [N, W] are read and written
# at their packed plane coordinates (`value_coords`).
#
# In 'hmem' mode the value plane lives in host memory; key-side processing
# never leaves HBM.  Position-based addressing means only the TOUCHED ROWS
# ever cross the tier: the row coordinates (and updates) move to host, the
# gather/scatter runs there (`compute_on('device_host')`), and just the
# result rows come back.  Every operand of a host computation is host-typed
# and the bodies create no constants, so no operand of another memory
# space can enter them.  'hbm' mode: plain device ops.
# ---------------------------------------------------------------------------

_HOST = jax.memory.Space.Host
_DEVICE = jax.memory.Space.Device


def _gather(values, prow, lanes):
    return values[prow[:, None], lanes]


def _scatter(add, values, prow, lane0, updates):
    # one [1, W] window per row at (prow, lane0); a window past the plane
    # is dropped.  (A per-element scatter is flattened to one dimension by
    # the TPU compiler when the plane has fewer than 2^31 elements, which
    # breaks the host-side computation's output layout.)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1))
    op = jax.lax.scatter_add if add else jax.lax.scatter
    return op(values, jnp.stack([prow, lane0], axis=1), updates, dims,
              mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _mask(width, values, keep):
    # keep [B*S] (per logical row) -> one all-ones/all-zeros word per plane
    # lane, ANDed in: constant-free, and a cleared lane is +0.0 whatever it
    # held (NaN included)
    r, lanes = values.shape
    pitch = value_pitch(width)
    per = lanes // pitch
    ity = jnp.dtype(f"int{jnp.dtype(values.dtype).itemsize * 8}")
    word = -keep.astype(ity).reshape(r, per, 1)
    word = jnp.broadcast_to(word, (r, per, pitch)).reshape(r, lanes)
    bits = jax.lax.bitcast_convert_type(values, ity)
    return jax.lax.bitcast_convert_type(bits & word, values.dtype)


def _mask_lanes(width, values, keep):
    # the device path: one select per slot position along a plane row, so
    # nothing the size of the plane is reshaped (XLA would transpose the
    # plane for that, and at 2^27 slots the temporaries exceed HBM)
    r, lanes = values.shape
    pitch = value_pitch(width)
    per = lanes // pitch
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1) // pitch
    for j in range(per):
        clear = (slot == j) & ~keep[j::per][:, None]
        values = jnp.where(clear, jnp.zeros((), values.dtype), values)
    return values


_host_gather = compute_on("device_host")(jax.jit(_gather))


@functools.lru_cache(maxsize=None)
def _host_update(fn, out_sharding):
    """`fn(static, plane, ...)` run on the host.  A concrete plane's new
    version is declared where the old one lives: without that, XLA stages
    the result through an HBM buffer the size of the plane.  (Under a
    trace, the enclosing jit declares it — see `jit_returning_table`.)"""
    return compute_on("device_host")(
        jax.jit(fn, static_argnums=0, out_shardings=out_sharding))


def _host_call(fn, static, values, *args):
    sharding = (values.sharding if _declares_memory_kinds()
                and not isinstance(values, jax.core.Tracer) else None)
    return _host_update(fn, sharding)(static, values, *args)


def _declares_memory_kinds() -> bool:
    # the CPU compiler has no host-placement annotation: there a plane's
    # memory kind is nominal and nothing is staged, so nothing is declared
    return jax.default_backend() == "tpu"


def _placement(tree):
    """`out_shardings` for the successor of `tree` (a table handle or
    state): every leaf stays where it lives now."""
    if not _declares_memory_kinds():
        return None
    return jax.tree.map(lambda x: getattr(x, "sharding", None), tree)


def jit_returning_table(fn, *, static_argnums=(), donate_argnums=()):
    """`jax.jit` for `fn(table, *args) -> (successor, rest)`, where the
    successor is `table`'s next version (a handle or state).  The
    successor is declared where `table` lives: a jit that returns a
    host-resident value plane without declaring it makes XLA stage the
    whole plane through HBM.  One compiled program per placement of the
    table handed in.  Where no placement is declared (the CPU), a table
    holding a host plane is not donated: its successor is typed device
    memory there, and the runtime refuses to alias the two."""
    jitted = {}

    def call(table, *args):
        where = _placement(table)
        donate = donate_argnums
        if where is None:
            key = None
            if donate and any(
                    getattr(x, "sharding", None) is not None
                    and x.sharding.memory_kind == "pinned_host"
                    for x in jax.tree.leaves(table)):
                key, donate = "host plane", ()
        else:
            key = (jax.tree.structure(where), tuple(jax.tree.leaves(where)))
        f = jitted.get(key)
        if f is None:
            kw = {} if where is None else {"out_shardings": (where, None)}
            f = jitted[key] = jax.jit(fn, static_argnums=static_argnums,
                                      donate_argnums=donate, **kw)
        return f(table, *args)

    return call


def _crosses(tier: str, values: jax.Array) -> bool:
    """Whether row access crosses to host memory: an 'hmem' plane, which
    must be host-resident — one found in device memory raises rather than
    run as an HBM table."""
    if tier != "hmem":
        return False
    space = jax.typeof(values).memory_space
    if space != _HOST:
        raise ValueError(
            f"'hmem' value plane {values.shape} is in {space} memory, not "
            f"pinned_host: place it with `place_on_host`")
    return True


def tier_gather(tier: str, values: jax.Array, rows: jax.Array,
                width: int) -> jax.Array:
    """Logical rows `rows` [N] of width `width` -> [N, width]."""
    prow, lane0 = value_coords(rows, width, values.shape[1])
    lanes = lane0[:, None] + jnp.arange(width, dtype=lane0.dtype)
    if not _crosses(tier, values):
        return _gather(values, prow, lanes)
    out = _host_gather(values, jax.device_put(prow, _HOST),
                       jax.device_put(lanes, _HOST))
    return jax.device_put(out, _DEVICE)


def tier_scatter(tier: str, values: jax.Array, rows: jax.Array,
                 updates: jax.Array, *, add: bool = False) -> jax.Array:
    """Write (or add) logical rows; rows past the last slot are dropped."""
    prow, lane0 = value_coords(rows, updates.shape[1], values.shape[1])
    updates = updates.astype(values.dtype)
    if not _crosses(tier, values):
        return _scatter(add, values, prow, lane0, updates)
    return _host_call(_scatter, add, values, jax.device_put(prow, _HOST),
                      jax.device_put(lane0, _HOST),
                      jax.device_put(updates, _HOST))


def tier_mask_rows(tier: str, values: jax.Array, keep: jax.Array,
                   width: int) -> jax.Array:
    """Zero every value row where ~keep [B*S] (the whole-plane masked clear
    the maintenance sweeps use).  In 'hmem' mode only the keep mask crosses
    to the host — the value rows themselves never leave their tier."""
    if not _crosses(tier, values):
        return _mask_lanes(width, values, keep)
    return _host_call(_mask, width, values, jax.device_put(keep, _HOST))


def advance_clock(state: HKVState) -> HKVState:
    """Tick the global LRU clock (one tick per batched op, paper's device clock)."""
    c = u64.add_u32(state.clock, jnp.uint32(1))
    return state._replace(clock_hi=c.hi, clock_lo=c.lo)


def set_epoch(state: HKVState, epoch) -> HKVState:
    return state._replace(epoch=jnp.asarray(epoch, jnp.uint32))


def value_row_index(bucket: jax.Array, slot: jax.Array, slots_per_bucket: int) -> jax.Array:
    """Position-based addressing (§3.6): value row = bucket * S + slot."""
    return bucket * slots_per_bucket + slot
