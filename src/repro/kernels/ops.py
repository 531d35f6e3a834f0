"""Jit'd wrappers over the Pallas kernels (+ dispatch, padding and tiling).

The raw kernels take `interpret` with no default; these wrappers are the
one place that chooses it (`default_interpret`: compiled on a TPU,
interpreted elsewhere).  They also pad every batch to the kernels' query
tile and cut it into chunks of at most `CHUNK` queries, because each
kernel holds its chunk's per-query scalars in SMEM: any batch size runs.
That padding, chunking and the unpacking and masking of each kernel's
outputs run under the named scope `hkv.kernel_io` (the kernel-wrapper
layer of a device profile; DESIGN.md §Observability).
The wrappers are drop-in equivalents of the pure-jnp paths in
`repro.core` and are cross-checked against them in tests.

Role taxonomy coverage (paper §3.5; see also `repro.core.ops`):

  READERS    kernel-backed here: find_fused_kernel / find_kernel /
             find_many_kernel (the FUSED find_scan path: digest pre-filter
             + full-key confirm + score readout + in-line value gather in
             ONE launch over both candidate bucket rows — DESIGN.md
             §Readers), locate_kernel (digest_scan tlp/pipeline; the
             metadata-only path behind find_ptr/contains and the updaters),
             bucket_stats_kernel (score_scan).  jnp-only: size/load_factor/
             export_* (trivial reductions/slices — nothing for a kernel to
             win).
  UPDATERS   kernel-backed here: update_rows_kernel (the FUSED update_scan
             pass: probe + full-key confirm + in-kernel sparse-optimizer
             apply + masked row write-back in ONE launch — DESIGN.md
             §Updaters; update_composed_kernel is the pre-fusion
             locate + gather + host apply + scatter baseline), assign_kernel
             (assign / assign_add via scatter_rows).  jnp-only:
             assign_scores (scalar metadata scatter, no value traffic).
  INSERTERS  kernel-backed here: upsert_kernel / insert_and_evict_kernel /
             find_or_insert_kernel — the fused upsert_scan path (probe +
             claim row passes plus gather/scatter value stages) sharing
             `core.merge.upsert`'s batch-closure orchestration, so results
             are bit-identical to the pure-jnp path (DESIGN.md §4).
             jnp-only: erase, clear, accum_or_assign.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import find as find_mod
from repro.core import merge as merge_mod
from repro.core import roles
from repro.core import table as table_mod
from repro.core import u64
from repro.core.table import HKVConfig, HKVState
from repro.core.u64 import U64
from repro.kernels import digest_scan as _ds
from repro.kernels import find_scan as _fs
from repro.kernels import gather as _ga
from repro.kernels import ref as _ref
from repro.kernels import scatter as _sc
from repro.kernels import score_scan as _ss
from repro.kernels import sweep_scan as _sw
from repro.kernels import update_scan as _upd
from repro.kernels import upsert_scan as _us


CHUNK = 4096  # queries per kernel call (their scalars live in SMEM)
KERNEL_IO = "hkv.kernel_io"
_io = roles.scoped(KERNEL_IO)


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, n: int, fill=0):
    if x.shape[0] == n:
        return x
    pad = jnp.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def _plan(n: int) -> tuple[int, int]:
    """(query tile, padded batch) for a batch of n queries."""
    if n <= CHUNK:
        q_tile = min(128, max(8, -(-n // 8) * 8))
        return q_tile, -(-n // q_tile) * q_tile
    return 128, -(-n // CHUNK) * CHUNK


def _chunks(args, fills, npad):
    """Pad per-query arrays to npad; split into [k, CHUNK, ...] if needed."""
    args = [_pad_to(a, npad, f) for a, f in zip(args, fills)]
    if npad <= CHUNK:
        return args, 0
    k = npad // CHUNK
    return [a.reshape((k, CHUNK) + a.shape[1:]) for a in args], k


@_io
def _per_query(call, args, fills):
    """Run a read-only per-query kernel `call(q_tile, *args)` over a batch:
    padded to its tile, one call per chunk.  Outputs trimmed to the batch."""
    n = args[0].shape[0]
    q_tile, npad = _plan(n)
    args, k = _chunks(args, fills, npad)
    if not k:
        outs = call(q_tile, *args)
    else:
        outs = jax.lax.map(lambda xs: call(q_tile, *xs), args)
        outs = jax.tree.map(lambda o: o.reshape((npad,) + o.shape[2:]), outs)
    return jax.tree.map(lambda o: o[:n], outs)


@_io
def _per_query_write(call, plane, args, fills):
    """`_per_query` for a kernel that rewrites the value plane:
    `call(q_tile, plane, *args) -> (out, plane)`, chunks in batch order."""
    n = args[0].shape[0]
    q_tile, npad = _plan(n)
    args, k = _chunks(args, fills, npad)
    if not k:
        out, plane = call(q_tile, plane, *args)
        return out[:n], plane

    def step(p, xs):
        out, p = call(q_tile, p, *xs)
        return p, out

    plane, out = jax.lax.scan(step, plane, args)
    return out.reshape(npad)[:n], plane


def locate_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
) -> find_mod.Locate:
    """Kernel-backed drop-in for core.find.locate (single & dual bucket)."""
    if interpret is None:
        interpret = default_interpret()
    probe = find_mod.probe_keys(cfg, keys)
    qd = probe.digest.astype(jnp.uint32)
    scan = lambda q_tile, *a: _ds.digest_scan(
        state.digests, state.key_hi, state.key_lo, *a,
        variant=variant, q_tile=q_tile, interpret=interpret)

    def run(bucket):
        slot, found = _per_query(
            scan, (bucket, qd, keys.hi, keys.lo),
            (0, 0, u64.EMPTY_HI, u64.EMPTY_LO))
        return slot, found.astype(bool)

    with jax.named_scope(KERNEL_IO):
        slot1, hit1 = run(probe.bucket1)
        if cfg.buckets_per_key == 2:
            slot2, hit2 = run(probe.bucket2)
            found = (hit1 | hit2) & probe.valid
            bucket = jnp.where(hit1, probe.bucket1, jnp.where(hit2, probe.bucket2, probe.bucket1))
            slot = jnp.where(hit1, slot1, jnp.where(hit2, slot2, 0))
        else:
            found = hit1 & probe.valid
            bucket, slot = probe.bucket1, jnp.where(hit1, slot1, 0)
        s = cfg.slots_per_bucket
        return find_mod.Locate(found=found, bucket=bucket, slot=slot, row=bucket * s + slot)


class FusedFind(NamedTuple):
    """Everything the fused find pass resolves per query, in one launch."""

    values: jax.Array    # [N, dim + aux] full-width hit rows (zeros on miss)
    found: jax.Array     # bool [N]
    bucket: jax.Array    # int32 [N] bucket holding the key (b1 on miss)
    slot: jax.Array      # int32 [N] slot holding the key (0 on miss)
    row: jax.Array       # int32 [N] value row = bucket * S + slot
    score_hi: jax.Array  # uint32 [N] hit entry scores (0 on miss)
    score_lo: jax.Array

    @property
    def loc(self) -> find_mod.Locate:
        return find_mod.Locate(found=self.found, bucket=self.bucket,
                               slot=self.slot, row=self.row)


def find_fused_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
) -> FusedFind:
    """The fused find pass (find_scan.py): digest pre-filter + full-key
    confirm + dual-bucket merge + score readout + in-line value gather, in
    ONE kernel launch — replacing the digest_scan (x buckets_per_key) +
    gather_rows composition and its on-host row-address round trip.

    Bit-identical to `core.find.locate` + `gather_values` + the score
    readout in `core.ops.find`/`find_rows` (the jnp oracle; pinned in
    tests/test_find_kernel.py).  Host-tier value planes ('hmem') keep the
    §3.6 crossing contract: the kernel locates, `tier_gather` moves rows.
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg.value_tier != "hbm":
        # host-tier rows cross via the jnp tier contract; metadata still
        # resolves on the kernel locate path
        loc = locate_kernel(state, cfg, keys, variant=variant,
                            interpret=interpret)
        vals = find_mod.gather_values(state, loc, None, cfg)
        shi = jnp.where(loc.found, state.score_hi[loc.bucket, loc.slot], 0)
        slo = jnp.where(loc.found, state.score_lo[loc.bucket, loc.slot], 0)
        return FusedFind(values=vals, found=loc.found, bucket=loc.bucket,
                         slot=loc.slot, row=loc.row, score_hi=shi,
                         score_lo=slo)

    probe = find_mod.probe_keys(cfg, keys)
    b2 = probe.bucket2 if cfg.buckets_per_key == 2 else probe.bucket1
    found, sel, slot, shi, slo, vals = _find_scan(
        (state.digests, state.key_hi, state.key_lo, state.score_hi,
         state.score_lo, state.values),
        cfg, probe.bucket1, b2, probe.digest.astype(jnp.uint32), keys,
        variant, interpret)
    # re-mask by probe validity: an EMPTY padding key may alias empty slots
    # in-kernel; the reference masks those out via probe.valid
    with jax.named_scope(KERNEL_IO):
        found = found.astype(bool) & probe.valid
        bucket = jnp.where(sel == 1, b2, probe.bucket1)
        return FusedFind(
            values=jnp.where(found[:, None], vals, 0),
            found=found,
            bucket=bucket,
            slot=slot,
            row=bucket * cfg.slots_per_bucket + slot,
            score_hi=jnp.where(found, shi, 0),
            score_lo=jnp.where(found, slo, 0),
        )


def _find_scan(planes, cfg: HKVConfig, b1, b2, qd, keys: U64, variant: str,
               interpret: bool):
    """The fused find kernel over a whole batch (padded and chunked)."""
    scan = lambda q_tile, *a: _fs.find_scan(
        *planes, *a, width=cfg.total_value_dim, variant=variant,
        q_tile=q_tile, use_digest=cfg.use_digest, interpret=interpret)
    return _per_query(scan, (b1, b2, qd, keys.hi, keys.lo),
                      (0, 0, 0, u64.EMPTY_HI, u64.EMPTY_LO))


def find_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
):
    """Kernel-backed `find`: ONE fused pass (was digest_scan + gather_rows)."""
    r = find_fused_kernel(state, cfg, keys, variant=variant,
                          interpret=interpret)
    return r.values[:, : cfg.dim], r.found


def find_composed_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
):
    """The pre-fusion composition — digest_scan locate (one launch per
    candidate bucket) + position-addressed gather_rows launch — kept as the
    launch-count/parity baseline the fused path is measured against
    (tests/test_find_kernel.py, benchmarks/exp2 `fused` arm)."""
    if interpret is None:
        interpret = default_interpret()
    loc = locate_kernel(state, cfg, keys, variant=variant, interpret=interpret)
    vals = _gather(state.values, cfg, loc.row, loc.found, interpret)
    return vals[:, : cfg.dim], loc.found


def find_many_kernel(
    states: Sequence[HKVState],
    cfg: HKVConfig,
    keys_list: Sequence[U64],
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
) -> list[FusedFind]:
    """Batched multi-table lookup: T same-geometry tables in ONE launch.

    The embedding layer keeps one table per feature; serving a wave used to
    launch one find per feature.  Same-geometry tables (same cfg) stack
    along the bucket axis — metadata planes [T*B, S], value plane
    [T*B*S, V] — and per-table probes offset their buckets by t*B, so the
    SAME fused kernel serves all features in a single grid.  Returns one
    `FusedFind` per table with table-local bucket/row indices.
    """
    if interpret is None:
        interpret = default_interpret()
    if not states:
        return []
    if cfg.value_tier != "hbm":
        raise ValueError("find_many_kernel requires the hbm value tier")
    b = cfg.num_buckets
    s = cfg.slots_per_bucket
    for st in states:
        if st.key_hi.shape != (b, s) or st.values.shape != states[0].values.shape:
            raise ValueError("find_many_kernel requires same-geometry tables")
    probes = [find_mod.probe_keys(cfg, k) for k in keys_list]
    counts = [k.hi.shape[0] for k in keys_list]
    off = lambda a, t: a + jnp.int32(t * b)
    b1 = jnp.concatenate([off(p.bucket1, t) for t, p in enumerate(probes)])
    b2s = [p.bucket2 if cfg.buckets_per_key == 2 else p.bucket1
           for p in probes]
    b2 = jnp.concatenate([off(x, t) for t, x in enumerate(b2s)])
    qd = jnp.concatenate([p.digest.astype(jnp.uint32) for p in probes])
    qh = jnp.concatenate([k.hi for k in keys_list])
    ql = jnp.concatenate([k.lo for k in keys_list])

    # stacked planes: table t's logical row g is global row t*B*S + g, and
    # its packed plane rows follow table t-1's, so the stacking agrees
    stack = lambda f: jnp.concatenate([f(st) for st in states], axis=0)
    planes = tuple(stack(lambda st, f=f: getattr(st, f)) for f in (
        "digests", "key_hi", "key_lo", "score_hi", "score_lo", "values"))
    found, sel, slot, shi, slo, vals = _find_scan(
        planes, cfg, b1, b2, qd, U64(qh, ql), variant, interpret)
    out: list[FusedFind] = []
    start = 0
    for t, (p, cnt) in enumerate(zip(probes, counts)):
        sl = slice(start, start + cnt)
        start += cnt
        f = found[sl].astype(bool) & p.valid
        b2_local = b2s[t]
        bucket = jnp.where(sel[sl] == 1, b2_local, p.bucket1)  # table-local
        out.append(FusedFind(
            values=jnp.where(f[:, None], vals[sl], 0),
            found=f,
            bucket=bucket,
            slot=slot[sl],
            row=bucket * s + slot[sl],
            score_hi=jnp.where(f, shi[sl], 0),
            score_lo=jnp.where(f, slo[sl], 0),
        ))
    return out


def assign_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    *,
    add: bool = False,
    interpret: bool | None = None,
) -> HKVState:
    """Kernel-backed updater (assign / assign_add).

    PRECONDITION: keys unique within the batch (callers dedupe; duplicate
    handling is the merge path's job).
    """
    if interpret is None:
        interpret = default_interpret()
    loc = locate_kernel(state, cfg, keys, interpret=interpret)
    vdim = cfg.total_value_dim
    if values.shape[1] < vdim:
        values = jnp.concatenate(
            [values, jnp.zeros((values.shape[0], vdim - values.shape[1]), values.dtype)],
            axis=1,
        )
    new_values = _scatter(state.values, cfg, loc.row, values, loc.found,
                          add, interpret)
    return state._replace(values=new_values)


@_io
def _gather(values, cfg: HKVConfig, rows, mask, interpret: bool):
    """gather_rows over a whole batch: logical rows, zeros where ~mask."""
    rows = jnp.clip(rows, 0, cfg.capacity - 1)
    call = lambda q_tile, r, m: _ga.gather_rows(
        values, r, m, width=cfg.total_value_dim, q_tile=q_tile,
        interpret=interpret)
    return _per_query(call, (rows, mask.astype(jnp.int32)), (0, 0))


@_io
def _scatter(values, cfg: HKVConfig, rows, updates, mask, add: bool,
             interpret: bool):
    """scatter_rows over a whole batch (masked-out lanes write nothing)."""
    rows = jnp.clip(rows, 0, cfg.capacity - 1)

    def call(q_tile, plane, r, u, m):
        return jnp.zeros((r.shape[0],), jnp.int32), _sc.scatter_rows(
            plane, r, u, m, add=add, q_tile=q_tile, interpret=interpret)

    return _per_query_write(
        call, values, (rows, updates.astype(values.dtype),
                       mask.astype(jnp.int32)), (0, 0, 0))[1]


class UpdateRows(NamedTuple):
    """Result of the fused updater pass: new state + which lanes trained."""

    state: HKVState
    found: jax.Array   # bool [N] — lane's key was resident and its row trained


def update_rows_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    grads: jax.Array,
    opt,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
) -> UpdateRows:
    """The fused updater pass (update_scan.py): probe + full-key confirm +
    in-kernel sparse-optimizer apply + masked row write-back in ONE kernel
    launch — replacing the locate + gather_rows + host `opt.apply` +
    scatter_rows composition and its 2x row traffic through HBM.

    PRECONDITION: keys unique within the batch, `grads` pre-summed per key
    (the embedding layer dedupes + segment-sums first).  Miss lanes and
    EMPTY padding never write (cache semantics: un-admitted keys do not
    train).  Bit-identical to `ref.update_scan_ref` and to the jnp
    `core.ops.update_rows` reference (pinned in tests/test_update_kernel.py).

    Host-tier value planes ('hmem') keep the §3.6 crossing contract: the
    kernel locates, the rows cross through tier_gather / tier_scatter with
    the optimizer applied on-device between the crossings.
    """
    if interpret is None:
        interpret = default_interpret()
    if cfg.value_tier != "hbm":
        loc = locate_kernel(state, cfg, keys, variant=variant,
                            interpret=interpret)
        rows = find_mod.gather_values(state, loc, None, cfg)
        new_rows = opt.apply(rows, grads, cfg.dim).astype(state.values.dtype)
        new_rows = jnp.where(loc.found[:, None], new_rows, rows)
        new_values = table_mod.tier_scatter(
            cfg.value_tier, state.values,
            jnp.where(loc.found, loc.row, cfg.capacity), new_rows)
        return UpdateRows(state=state._replace(values=new_values),
                          found=loc.found)

    probe = find_mod.probe_keys(cfg, keys)
    b2 = probe.bucket2 if cfg.buckets_per_key == 2 else probe.bucket1

    def call(q_tile, plane, *a):
        return _upd.update_scan(
            state.digests, state.key_hi, state.key_lo, plane, *a, opt=opt,
            dim=cfg.dim, width=cfg.total_value_dim, variant=variant,
            q_tile=q_tile, use_digest=cfg.use_digest, interpret=interpret)

    # the qvalid gate travels INTO the kernel: EMPTY padding lanes match
    # empty slots, and unlike the read-only find pass an updater cannot
    # re-mask after the fact — the gate must dominate the store
    found, new_values = _per_query_write(
        call, state.values,
        (probe.bucket1, b2, probe.digest.astype(jnp.uint32), keys.hi,
         keys.lo, probe.valid.astype(jnp.int32),
         grads.astype(state.values.dtype)),
        (0, 0, 0, u64.EMPTY_HI, u64.EMPTY_LO, 0, 0))
    return UpdateRows(state=state._replace(values=new_values),
                      found=found.astype(bool))


def update_composed_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    grads: jax.Array,
    opt,
    *,
    variant: str = "pipeline",
    interpret: bool | None = None,
) -> UpdateRows:
    """The pre-fusion updater composition — locate (one digest_scan launch
    per candidate bucket) + gather_rows + host-jnp `opt.apply` + scatter_rows
    — kept as the launch-count/parity baseline the fused pass is measured
    against (tests/test_update_kernel.py, benchmarks/exp9)."""
    if interpret is None:
        interpret = default_interpret()
    loc = locate_kernel(state, cfg, keys, variant=variant, interpret=interpret)
    rows = _gather(state.values, cfg, loc.row, loc.found, interpret)
    new_rows = opt.apply(rows, grads, cfg.dim).astype(state.values.dtype)
    new_values = _scatter(state.values, cfg, loc.row, new_rows, loc.found,
                          False, interpret)
    return UpdateRows(state=state._replace(values=new_values),
                      found=loc.found)


def sweep_mask_kernel(state: HKVState, cfg: HKVConfig, pred,
                      *, interpret: bool | None = None) -> jax.Array:
    """Kernel-backed predicate mask for the maintenance sweeps
    (`core.ops.erase_if` / `evict_if`): one fused pass over the metadata
    planes evaluating `pred` (a `core.predicates.SweepPredicate`) with
    liveness gating and per-bucket counting.  Returns the bool [B, S]
    match mask; bit-identical to the jnp reference because both evaluate
    `core.predicates.match_planes` (DESIGN.md §Maintenance)."""
    if interpret is None:
        interpret = default_interpret()
    match, _cnt = _sw.sweep_match(
        state.key_hi, state.key_lo, state.score_hi, state.score_lo,
        pred.a_hi, pred.a_lo, pred.b_hi, pred.b_lo,
        kind=pred.kind, interpret=interpret,
    )
    return match


def bucket_stats_kernel(state: HKVState, *, interpret: bool | None = None):
    """(occ, min_hi, min_lo, argmin) per bucket via the tiled scan kernel."""
    if interpret is None:
        interpret = default_interpret()
    b = state.key_hi.shape[0]
    tile = 8 if b % 8 == 0 else b
    return _ss.bucket_stats(
        state.key_hi, state.key_lo, state.score_hi, state.score_lo,
        bucket_tile=tile, interpret=interpret,
    )


# =============================================================================
# Inserter path: fused upsert/evict kernels (upsert_scan + gather/scatter)
# =============================================================================


def _upsert_probe(state: HKVState, cfg: HKVConfig, probe, qd, keys: U64,
                  interpret: bool):
    """upsert_probe over a whole batch (padded and chunked)."""
    scan = lambda q_tile, *a: _us.upsert_probe(
        state.digests, state.key_hi, state.key_lo, state.score_hi,
        state.score_lo, *a, q_tile=q_tile, use_digest=cfg.use_digest,
        interpret=interpret)
    return _per_query(scan, (probe.bucket1, probe.bucket2, qd, keys.hi,
                             keys.lo), (0, 0, 0, u64.EMPTY_HI, u64.EMPTY_LO))


def _kernel_locate_stage(cfg: HKVConfig, interpret: bool):
    """UpsertStages.locate backed by the kernel match path.

    Single-bucket mode reuses the digest-scan reader kernel (3 planes, one
    row per query); dual mode uses the fused upsert_probe so both candidate
    rows stream through one pass instead of two kernel launches.
    """

    def locate_single(state: HKVState, _cfg: HKVConfig, keys: U64, probe):
        return locate_kernel(state, cfg, keys, interpret=interpret)

    if cfg.buckets_per_key == 1:
        return locate_single

    @_io
    def locate(state: HKVState, _cfg: HKVConfig, keys: U64, probe):
        found, hit_sel, hit_slot, _tgt = _upsert_probe(
            state, cfg, probe, probe.digest.astype(jnp.uint32), keys,
            interpret)
        fnd = found.astype(bool) & probe.valid
        bucket = jnp.where(
            found.astype(bool) & (hit_sel == 1), probe.bucket2, probe.bucket1
        )
        s = cfg.slots_per_bucket
        return find_mod.Locate(
            found=fnd, bucket=bucket, slot=hit_slot, row=bucket * s + hit_slot
        )

    return locate


def _kernel_select_stage(cfg: HKVConfig, interpret: bool):
    """UpsertStages.select_target backed by the same fused probe pass.

    Runs against the post-phase-1 state (hit scores already updated), as the
    batch closure requires: D2's lower-min-score comparison must see this
    batch's score touches.
    """

    @_io
    def select(state: HKVState, _cfg: HKVConfig, probe):
        if cfg.buckets_per_key == 1:
            return probe.bucket1
        zeros = jnp.zeros_like(probe.bucket1, jnp.uint32)
        # match result unused: stats-only pass
        _f, _hs, _sl, tgt_sel = _upsert_probe(
            state, cfg, probe, zeros, U64(zeros, zeros), interpret)
        return jnp.where(tgt_sel == 1, probe.bucket2, probe.bucket1)

    return select


def _kernel_victim_stage(cfg: HKVConfig, interpret: bool):
    """UpsertStages.victim_at_rank backed by the claim_scan rank kernel."""

    @_io
    def victim(state: HKVState, _cfg: HKVConfig, bkt_g, rank):
        s = cfg.slots_per_bucket
        scan = lambda q_tile, *a: _us.claim_scan(
            state.key_hi, state.key_lo, state.score_hi, state.score_lo, *a,
            q_tile=q_tile, interpret=interpret)
        vslot, vocc, vsh, vsl, vkh, vkl = _per_query(
            scan, (bkt_g, jnp.clip(rank, 0, s - 1)), (0, 0))
        return vslot, vocc.astype(bool), U64(vsh, vsl), U64(vkh, vkl)

    return victim


def _kernel_gather_stage(cfg: HKVConfig, interpret: bool):
    jnp_gather = merge_mod.jnp_stages().gather_values

    def gather(_cfg: HKVConfig, values, rows, mask):
        if cfg.value_tier != "hbm":  # host-tier rows cross via the jnp path
            return jnp_gather(cfg, values, rows, mask)
        return _gather(values, cfg, rows, mask, interpret)

    return gather


def _kernel_scatter_stage(cfg: HKVConfig, interpret: bool):
    jnp_scatter = merge_mod.jnp_stages().scatter_values

    def scatter(_cfg: HKVConfig, values, rows, updates, mask):
        if cfg.value_tier != "hbm":
            return jnp_scatter(cfg, values, rows, updates, mask)
        return _scatter(values, cfg, rows, updates, mask, False, interpret)

    return scatter


def kernel_stages(cfg: HKVConfig, *, interpret: bool | None = None
                  ) -> merge_mod.UpsertStages:
    """Kernel-backed implementations of every upsert stage contract."""
    if interpret is None:
        interpret = default_interpret()
    return merge_mod.UpsertStages(
        locate=_kernel_locate_stage(cfg, interpret),
        select_target=_kernel_select_stage(cfg, interpret),
        victim_at_rank=_kernel_victim_stage(cfg, interpret),
        gather_values=_kernel_gather_stage(cfg, interpret),
        scatter_values=_kernel_scatter_stage(cfg, interpret),
    )


def upsert_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    *,
    custom_scores: Optional[U64] = None,
    write_hit_values: bool = True,
    update_hit_scores: bool = True,
    insert_values: Optional[jax.Array] = None,
    return_evicted: bool = False,
    interpret: bool | None = None,
) -> merge_mod.MergeResult:
    """Kernel-backed drop-in for core.merge.upsert (Alg. 2/3 batch closure).

    Same orchestration, kernel stages: one fused probe pass (digest
    pre-filter -> full-key match -> occupancy/min-score -> dual-bucket
    selection), one claim pass (rank-r empty claim / argmin eviction /
    rejection), and gather/scatter row kernels for the value plane.
    Bit-identical to the pure-jnp path — statuses, evicted pairs, state.
    """
    return merge_mod.upsert(
        state, cfg, keys, values,
        custom_scores=custom_scores,
        write_hit_values=write_hit_values,
        update_hit_scores=update_hit_scores,
        insert_values=insert_values,
        return_evicted=return_evicted,
        stages=kernel_stages(cfg, interpret=interpret),
    )


def insert_and_evict_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    *,
    custom_scores: Optional[U64] = None,
    interpret: bool | None = None,
) -> merge_mod.MergeResult:
    """Kernel-backed insert_or_assign returning displaced entries in-launch
    (the paper's single-kernel eviction hand-off)."""
    return upsert_kernel(
        state, cfg, keys, values, custom_scores=custom_scores,
        return_evicted=True, interpret=interpret,
    )


def gather_rows_kernel(state: HKVState, cfg: HKVConfig, loc: find_mod.Locate,
                       dim: int, *, interpret: bool | None = None) -> jax.Array:
    """Position-addressed value gather at `loc` via the row-pipeline kernel
    (hbm tier only — tier crossings stay on the jnp `tier_gather` path).
    Missing keys return zero rows, matching `find_mod.gather_values`."""
    if interpret is None:
        interpret = default_interpret()
    return _gather(state.values, cfg, loc.row, loc.found, interpret)[:, :dim]


def find_or_insert_kernel(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    init_values: jax.Array,
    *,
    custom_scores: Optional[U64] = None,
    interpret: bool | None = None,
):
    """Kernel-backed find_or_insert: ONE fused probe pass.

    The upsert closure publishes every key's post-op location
    (`MergeResult.loc`), so neither the pre-locate nor the post-insert
    re-locate this wrapper used to issue is needed: `found` comes from the
    closure's own probe and the value readback is a position-addressed
    `gather_rows` at the published rows.  Probe passes: the closure's
    locate + target-selection stages only (pinned, with bit-parity against
    the old three-pass sequence, in tests/test_upsert_kernel.py).

    Returns (state, values, found, status) with core.ops.find_or_insert
    semantics: hits keep their stored value, rejected keys get the caller's
    init row back (ephemeral).
    """
    if interpret is None:
        interpret = default_interpret()
    res = upsert_kernel(
        state, cfg, keys, init_values, custom_scores=custom_scores,
        write_hit_values=False, interpret=interpret,
    )
    if cfg.value_tier == "hbm":
        vals = gather_rows_kernel(res.state, cfg, res.loc, cfg.dim,
                                  interpret=interpret)
    else:
        vals = find_mod.gather_values(res.state, res.loc, cfg.dim, cfg)
    with jax.named_scope(KERNEL_IO):
        vals = jnp.where(res.loc.found[:, None], vals,
                         init_values[:, : cfg.dim])
    return res.state, vals, res.found, res.status


# Re-exported oracles for tests/benches
ref = _ref
