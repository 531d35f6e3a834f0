"""HKV op engine (paper §4.1) — STL-style ops over a pure-functional state.

NOTE (API layering, DESIGN.md §API layer): the *public* surface is the
`HKVTable` handle in `repro.core.api`, which binds (state, cfg, backend)
once and normalizes key dtypes; these free functions remain the single
underlying implementation the handle delegates to.  New consumer code
should prefer `HKVTable` / `table.session()`; call these directly only
from inside `repro.core` / `repro.kernels` or where the unbound form is
genuinely needed (e.g. custom shard_map bodies).

Non-structural ops accept an optional precomputed `loc=` (a
`find.Locate` for the same key batch against a state with identical key
planes) so an op session can share one probe across commuting ops.

Triple-group role taxonomy (paper §3.5) survives on TPU as *dependency
structure* rather than a lock protocol (DESIGN.md §2):

  READERS   (find, find_ptr, contains, size, load_factor, export_batch*):
            consume the state, produce no new state.  XLA may fuse and
            reorder them freely — they commute with each other.
  UPDATERS  (assign, assign_add, assign_scores, update_rows): produce a
            new state but touch only values/scores of *existing* keys — no
            slot allocation, no digest writes, no eviction.  Two updater
            ops on disjoint keys commute; the training step exploits this
            by fusing gradient-assign with the forward lookup.
            `update_rows` is the gradient step proper: it applies a static
            `SparseOptimizer` variant to each resident key's full row, and
            on backend='kernel' runs the FUSED update_scan pass — probe +
            in-kernel optimizer apply + masked write-back in ONE launch
            (was locate + gather + host apply + scatter, ≥3 launches and
            2× row traffic through HBM).
  INSERTERS (insert_or_assign, find_or_insert, insert_and_evict, erase,
            clear): structural — bucket membership changes.  These are the
            only ops that form serialization points in a step schedule.

Every op is batch-synchronous, jittable, static-shape, and accepts the
EMPTY sentinel (0xFFFF_FFFF_FFFF_FFFF) as a padding key that is ignored.

Kernel backends (DESIGN.md §4, §Readers): the hot ops exist in two
implementations — the pure-jnp reference in this package and the Pallas
kernel path in `repro.kernels` — selected by a
`backend='auto'|'jnp'|'kernel'` argument.  READERS find/find_rows dispatch
to the FUSED find_scan pass (`repro.kernels.ops.find_fused_kernel`: digest
pre-filter + full-key confirm + score readout + in-line value gather in
one launch); find_ptr/contains take the metadata-only locate kernel; when
a session supplies a shared `loc=`, the value stage alone runs on the
kernel (gather_rows).  The INSERTERS insert_or_assign, insert_and_evict,
and find_or_insert dispatch to the fused upsert_scan path
(`repro.kernels.ops.upsert_kernel`), which shares this module's
batch-closure orchestration; the sweeps erase_if/evict_if dispatch their
mask stage.  Every kernel path is bit-identical to its jnp reference.
'auto' resolves to 'kernel' on TPU and 'jnp' elsewhere (off-TPU the
kernels run in interpret mode — correct but slow, so it is opt-in).
size/export_batch*, assign_scores, erase, clear, and accum_or_assign
remain jnp-only: they are trivial reductions or metadata-plane scatters
with no kernel to win.

Telemetry channel (DESIGN.md §Observability): every keyed op takes an
optional keyword-only `telemetry=` sink (`repro.obs.TelemetrySink`).
When supplied, the op records a device-computed `OpTelemetry` counter
record (probes, digest-prefilter passes, hits/misses, the upsert status
histogram) — computed by a pure OBSERVER over the pre-op state using the
same `probe_keys`/`match_lanes` formulas the op itself uses, so both
backends report identical numbers and op results stay bit-identical.
`telemetry=None` (the default) is literally the pre-telemetry code path:
the observer import and every counter expression live inside the
`telemetry is not None` branch, so the default adds zero launches and
zero jaxpr growth (pinned by tests/test_obs.py).  Whole-table scans
(size, load_factor, export_batch*) and clear carry no per-key probe and
are exempt (`repro.analysis.telemetry.TELEMETRY_EXEMPT`).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import find as find_mod
from repro.core import roles
from repro.core import merge as merge_mod
from repro.core import table as table_mod
from repro.core import u64
from repro.core.merge import (
    STATUS_EVICTED,
    STATUS_INSERTED,
    STATUS_INVALID,
    STATUS_REJECTED,
    STATUS_UPDATED,
    EvictionStream,
    MergeResult,
)
from repro.core.table import HKVConfig, HKVState
from repro.core.u64 import U64

def _obs():
    """Deferred observer import — only the `telemetry is not None` branch
    pays it, keeping the default path free of the obs subsystem."""
    from repro.obs import telemetry as obs_telemetry

    return obs_telemetry


# =============================================================================
# Readers
# =============================================================================


class FindResult(NamedTuple):
    values: jax.Array   # [N, dim] (zeros where not found)
    found: jax.Array    # bool [N]
    score_hi: jax.Array  # uint32 [N] (0 where not found)
    score_lo: jax.Array


def _fused_find(state: HKVState, cfg: HKVConfig, keys: U64, backend: str):
    """The reader-side kernel dispatch: the fused find_scan pass when the
    backend resolves to 'kernel', else None (caller falls through to the
    jnp reference).  One launch resolves match + scores + values."""
    if _resolve_backend(backend) != "kernel":
        return None
    from repro.kernels import ops as kernel_ops  # deferred: kernels import core

    return kernel_ops.find_fused_kernel(state, cfg, keys)


def _gather_shared(state: HKVState, cfg: HKVConfig, loc, dim):
    """Value gather at a caller-supplied (session-shared) locate — kernel
    row pipeline on the hbm tier, jnp `tier_gather` otherwise."""
    if cfg.value_tier == "hbm":
        from repro.kernels import ops as kernel_ops

        return kernel_ops.gather_rows_kernel(
            state, cfg, loc, cfg.total_value_dim if dim is None else dim)
    return find_mod.gather_values(state, loc, dim, cfg)


@roles.reader
def find(state: HKVState, cfg: HKVConfig, keys: U64,
         loc: Optional[find_mod.Locate] = None, *,
         backend: str = "auto", telemetry=None) -> FindResult:
    """Reader. Digest-accelerated lookup with value copy (paper `find`).

    backend='kernel' (or 'auto' on TPU) runs the FUSED find_scan pass when
    no shared `loc` is supplied: probe, match, score readout, and value
    gather in one kernel launch.  With a session-shared `loc`, the value
    stage alone runs on the kernel.  Bit-identical either way.

    Consumer code: prefer `HKVTable.find` / `session.find` (repro.core.api).
    """
    if loc is None:
        r = _fused_find(state, cfg, keys, backend)
        if r is not None:
            if telemetry is not None:
                telemetry.record(
                    "find", _obs().observe_find(state, cfg, keys, r.found))
            return FindResult(values=r.values[:, : cfg.dim], found=r.found,
                              score_hi=r.score_hi, score_lo=r.score_lo)
        loc = find_mod.locate(state, cfg, keys)
        vals = find_mod.gather_values(state, loc, cfg.dim, cfg)
    elif _resolve_backend(backend) == "kernel":
        vals = _gather_shared(state, cfg, loc, cfg.dim)
    else:
        vals = find_mod.gather_values(state, loc, cfg.dim, cfg)
    if telemetry is not None:
        telemetry.record(
            "find", _obs().observe_find(state, cfg, keys, loc.found))
    shi = jnp.where(loc.found, state.score_hi[loc.bucket, loc.slot], 0)
    slo = jnp.where(loc.found, state.score_lo[loc.bucket, loc.slot], 0)
    return FindResult(values=vals, found=loc.found, score_hi=shi, score_lo=slo)


@roles.reader
def find_ptr(state: HKVState, cfg: HKVConfig, keys: U64, *,
             backend: str = "auto", telemetry=None) -> find_mod.Locate:
    """Reader. The paper's pointer-returning `find*`: key-side work only.

    Returns position handles (bucket, slot, row) instead of copying values —
    the position-based addressing contract of §3.6 means `row` *is* the
    value address.  Dimension-independent, like the paper's ~7 B-KV/s path.
    backend='kernel' runs the metadata-only digest_scan locate (no value
    traffic — the fused pass would fetch rows this op must not touch).
    """
    if _resolve_backend(backend) == "kernel":
        from repro.kernels import ops as kernel_ops

        loc = kernel_ops.locate_kernel(state, cfg, keys)
    else:
        loc = find_mod.locate(state, cfg, keys)
    if telemetry is not None:
        telemetry.record(
            "find_ptr", _obs().observe_find(state, cfg, keys, loc.found))
    return loc


@roles.reader
def contains(state: HKVState, cfg: HKVConfig, keys: U64,
             loc: Optional[find_mod.Locate] = None, *,
             backend: str = "auto", telemetry=None) -> jax.Array:
    """Reader. Membership only (no value traffic)."""
    if loc is None:
        loc = find_ptr(state, cfg, keys, backend=backend)
    if telemetry is not None:
        telemetry.record(
            "contains", _obs().observe_find(state, cfg, keys, loc.found))
    return loc.found


class FindRowsResult(NamedTuple):
    rows: jax.Array     # [N, dim + aux] full-width table rows (zeros on miss)
    found: jax.Array    # bool [N]
    row: jax.Array      # int32 [N] value-plane row index (position addressing)
    score_hi: jax.Array  # uint32 [N] entry scores (0 where not found) — the
    score_lo: jax.Array  # tier hierarchy translates these on promotion


@roles.reader
def find_rows(state: HKVState, cfg: HKVConfig, keys: U64,
              loc: Optional[find_mod.Locate] = None, *,
              backend: str = "auto", telemetry=None) -> FindRowsResult:
    """Reader. Full-width row gather (embedding + aux optimizer columns).

    The sparse-optimizer path: gathers the entire stored row so slot state
    colocated with the embedding travels with it.  Missing keys return
    zero rows — callers must mask by `found` (the usual consumer, a
    row-refresh via `assign`, drops misses anyway).  Scores ride along so
    a promotion (`core/tiered.py`) can move an entry between tiers without
    a second metadata probe.  backend='kernel' takes the same fused
    find_scan pass as `find` — the kernel already gathers full-width rows
    and reads out scores, so this op is one launch too."""
    if loc is None:
        r = _fused_find(state, cfg, keys, backend)
        if r is not None:
            if telemetry is not None:
                telemetry.record(
                    "find_rows",
                    _obs().observe_find(state, cfg, keys, r.found))
            return FindRowsResult(rows=r.values, found=r.found, row=r.row,
                                  score_hi=r.score_hi, score_lo=r.score_lo)
        loc = find_mod.locate(state, cfg, keys)
        rows = find_mod.gather_values(state, loc, None, cfg)
    elif _resolve_backend(backend) == "kernel":
        rows = _gather_shared(state, cfg, loc, None)
    else:
        rows = find_mod.gather_values(state, loc, None, cfg)
    if telemetry is not None:
        telemetry.record(
            "find_rows", _obs().observe_find(state, cfg, keys, loc.found))
    shi = jnp.where(loc.found, state.score_hi[loc.bucket, loc.slot], 0)
    slo = jnp.where(loc.found, state.score_lo[loc.bucket, loc.slot], 0)
    return FindRowsResult(rows=rows, found=loc.found, row=loc.row,
                          score_hi=shi, score_lo=slo)


@roles.reader
def size(state: HKVState) -> jax.Array:
    """Reader. Number of live entries."""
    return jnp.sum(state.occupied_mask().astype(jnp.int32))


@roles.reader
def load_factor(state: HKVState) -> jax.Array:
    return state.load_factor()


class ExportResult(NamedTuple):
    key_hi: jax.Array
    key_lo: jax.Array
    values: jax.Array
    score_hi: jax.Array
    score_lo: jax.Array
    mask: jax.Array   # bool — live & predicate-matching entries


@roles.reader
def export_batch(
    state: HKVState, cfg: HKVConfig, bucket_start: int, bucket_count: int
) -> ExportResult:
    """Reader. Stream a contiguous bucket range to the caller (checkpointing).

    Static-shape: returns bucket_count*S entries with a liveness mask.
    Value rows cross tiers through `tier_gather`, so an 'hmem' table's
    checkpoint export honors the explicit host<->device crossing contract
    (§3.6) instead of slicing the host-resident plane in device code.
    """
    sl = slice(bucket_start, bucket_start + bucket_count)
    khi = state.key_hi[sl].reshape(-1)
    klo = state.key_lo[sl].reshape(-1)
    mask = ~u64.is_empty(U64(khi, klo))
    s = cfg.slots_per_bucket
    rows = table_mod.tier_gather(
        cfg.value_tier, state.values,
        jnp.arange(bucket_start * s, (bucket_start + bucket_count) * s,
                   dtype=jnp.int32),
        cfg.total_value_dim,
    )
    return ExportResult(
        key_hi=khi,
        key_lo=klo,
        values=rows,
        score_hi=state.score_hi[sl].reshape(-1),
        score_lo=state.score_lo[sl].reshape(-1),
        mask=mask,
    )


@roles.reader
def export_batch_if(
    state: HKVState,
    cfg: HKVConfig,
    bucket_start: int,
    bucket_count: int,
    score_threshold: U64,
) -> ExportResult:
    """Reader. export_batch with a score >= threshold predicate (paper §4.1)."""
    out = export_batch(state, cfg, bucket_start, bucket_count)
    ge = u64.ge(U64(out.score_hi, out.score_lo), score_threshold)
    return out._replace(mask=out.mask & ge)


# =============================================================================
# Updaters (non-structural writes)
# =============================================================================


@roles.updater
def assign(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    update_scores: bool = False,
    loc: Optional[find_mod.Locate] = None,
    *,
    telemetry=None,
) -> HKVState:
    """Updater. Write values of *existing* keys in place; misses are no-ops.

    Never allocates slots, never evicts, never touches digests — the
    non-structural contract that lets updater batches run concurrently in
    the paper and fuse freely under XLA here.

    Consumer code: prefer `HKVTable.assign` / `session.assign`.
    """
    if loc is None:
        loc = find_mod.locate(state, cfg, keys)
    if telemetry is not None:
        telemetry.record(
            "assign", _obs().observe_update(state, cfg, keys, loc.found))
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    # last-writer-wins on within-batch duplicates: scatter in batch order
    row = jnp.where(loc.found, loc.row, b * s)
    vdim = cfg.total_value_dim
    if values.shape[1] < vdim:  # caller wrote only the embedding columns
        pad = jnp.zeros((values.shape[0], vdim - values.shape[1]), state.values.dtype)
        old = table_mod.tier_gather(
            cfg.value_tier, state.values, jnp.clip(loc.row, 0, b * s - 1),
            vdim)[:, values.shape[1]:]
        values = jnp.concatenate([values, jnp.where(loc.found[:, None], old, pad)], axis=1)
    new_values = table_mod.tier_scatter(
        cfg.value_tier, state.values, row, values.astype(state.values.dtype)
    )
    state = state._replace(values=new_values)
    if update_scores:
        state = table_mod.advance_clock(state)
        ones = jnp.ones((keys.hi.shape[0],), jnp.uint32)
        new_sc = cfg.policy.update_score(
            U64(state.score_hi[loc.bucket, loc.slot], state.score_lo[loc.bucket, loc.slot]),
            state.clock,
            state.epoch,
            ones,
            None,
        )
        hb = jnp.where(loc.found, loc.bucket, b)
        state = state._replace(
            score_hi=state.score_hi.at[hb, loc.slot].set(new_sc.hi, mode="drop"),
            score_lo=state.score_lo.at[hb, loc.slot].set(new_sc.lo, mode="drop"),
        )
    return state


@roles.updater
def assign_add(
    state: HKVState, cfg: HKVConfig, keys: U64, deltas: jax.Array,
    loc: Optional[find_mod.Locate] = None,
    *,
    telemetry=None,
) -> HKVState:
    """Updater. values[k] += delta for existing keys (duplicates accumulate).

    This is the embedding-gradient path: sparse grads apply as a
    non-structural scatter-add, the TPU analogue of the paper's concurrent
    updater kernels.
    """
    if loc is None:
        loc = find_mod.locate(state, cfg, keys)
    if telemetry is not None:
        telemetry.record(
            "assign_add", _obs().observe_update(state, cfg, keys, loc.found))
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    row = jnp.where(loc.found, loc.row, b * s)
    if deltas.shape[1] < cfg.total_value_dim:
        pad = jnp.zeros(
            (deltas.shape[0], cfg.total_value_dim - deltas.shape[1]), state.values.dtype
        )
        deltas = jnp.concatenate([deltas.astype(state.values.dtype), pad], axis=1)
    return state._replace(values=table_mod.tier_scatter(
        cfg.value_tier, state.values, row, deltas.astype(state.values.dtype), add=True
    ))


@roles.updater
def assign_scores(
    state: HKVState, cfg: HKVConfig, keys: U64, scores: U64,
    loc: Optional[find_mod.Locate] = None,
    *,
    telemetry=None,
) -> HKVState:
    """Updater. Overwrite scores of existing keys (paper `assign_scores`)."""
    if loc is None:
        loc = find_mod.locate(state, cfg, keys)
    if telemetry is not None:
        telemetry.record(
            "assign_scores",
            _obs().observe_update(state, cfg, keys, loc.found))
    hb = jnp.where(loc.found, loc.bucket, cfg.num_buckets)
    return state._replace(
        score_hi=state.score_hi.at[hb, loc.slot].set(scores.hi, mode="drop"),
        score_lo=state.score_lo.at[hb, loc.slot].set(scores.lo, mode="drop"),
    )


class RowUpdate(NamedTuple):
    """Structured updater payload for the gradient step (`update_rows`).

    `OpSession.update_rows` accepts this in place of an opaque callable:
    a static `SparseOptimizer` variant plus the per-key (deduped,
    segment-summed) gradient rows.  Being structured — the planner can see
    *what* the update is — lets the session route the whole op to the
    fused update_scan kernel instead of forcing the generic
    locate/gather/fn/scatter decomposition.
    """

    opt: Any            # SparseOptimizer (hashable/static — selects the variant)
    grads: jax.Array    # [N, dim] segment-summed gradient rows


class UpdateRowsResult(NamedTuple):
    state: HKVState
    found: jax.Array    # bool [N] — lane's key was resident and its row trained


@roles.updater
def update_rows(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    grads: jax.Array,
    opt,
    *,
    update_scores: bool = False,
    loc: Optional[find_mod.Locate] = None,
    backend: str = "auto",
    telemetry=None,
) -> UpdateRowsResult:
    """Updater. The gradient step: apply the sparse optimizer `opt` (a
    static `SparseOptimizer` variant) to each *existing* key's full row
    [embedding | aux slot state] in place.  Misses are no-ops — cache
    semantics: un-admitted keys never train.

    PRECONDITION: keys unique within the batch, `grads` pre-summed per key
    (`HKVEmbedding.apply_grads` dedupes + segment-sums before calling).

    backend='kernel' (or 'auto' on TPU) with no shared `loc` and no score
    touch runs the FUSED update_scan pass: probe + full-key confirm +
    in-kernel optimizer apply + masked row write-back in ONE kernel launch
    (was locate + gather_rows + host `opt.apply` + scatter_rows — ≥3
    launches and 2× row traffic).  With a session-shared `loc` or
    `update_scores=True`, the value stages run composed against that
    locate.  Bit-identical either way (pinned in
    tests/test_update_kernel.py).

    Consumer code: prefer `session.update_rows` with a `RowUpdate` payload.
    """
    if (loc is None and not update_scores
            and _resolve_backend(backend) == "kernel"):
        from repro.kernels import ops as kernel_ops  # deferred: kernels import core

        r = kernel_ops.update_rows_kernel(state, cfg, keys, grads, opt)
        if telemetry is not None:
            telemetry.record(
                "update_rows",
                _obs().observe_update(state, cfg, keys, r.found))
        return UpdateRowsResult(state=r.state, found=r.found)
    if loc is None:
        loc = find_mod.locate(state, cfg, keys)
        rows = find_mod.gather_values(state, loc, None, cfg)
    elif _resolve_backend(backend) == "kernel":
        rows = _gather_shared(state, cfg, loc, None)
    else:
        rows = find_mod.gather_values(state, loc, None, cfg)
    if telemetry is not None:
        telemetry.record(
            "update_rows", _obs().observe_update(state, cfg, keys, loc.found))
    new_rows = opt.apply(rows, grads, cfg.dim).astype(state.values.dtype)
    new_rows = jnp.where(loc.found[:, None], new_rows, rows)
    state = assign(state, cfg, keys, new_rows, update_scores=update_scores,
                   loc=loc)
    return UpdateRowsResult(state=state, found=loc.found)


# =============================================================================
# Inserters (structural writes)
# =============================================================================


class UpsertResult(NamedTuple):
    state: HKVState
    status: jax.Array  # int8 [N]: 0 invalid / 1 updated / 2 inserted / 3 evicted / 4 rejected


def _resolve_backend(backend: str) -> str:
    """'auto' picks the Pallas path on TPU and jnp elsewhere: off-TPU the
    kernels execute in interpret mode, which validates semantics but is far
    slower than XLA — callers opt in explicitly with backend='kernel'."""
    if backend == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ("jnp", "kernel"):
        raise ValueError(
            f"unknown backend {backend!r}; one of 'auto'|'jnp'|'kernel'"
        )
    return backend


def _upsert_stages(backend: str, cfg: HKVConfig):
    """Resolve a backend name to UpsertStages (None = pure-jnp defaults)."""
    if _resolve_backend(backend) == "jnp":
        return None
    from repro.kernels import ops as kernel_ops  # deferred: kernels import core

    return kernel_ops.kernel_stages(cfg)


@roles.inserter
def insert_or_assign(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    custom_scores: Optional[U64] = None,
    *,
    backend: str = "auto",
    telemetry=None,
) -> UpsertResult:
    """Inserter. Update-or-insert with in-line eviction/admission (Alg. 2/3).

    Consumer code: prefer `HKVTable.insert_or_assign` (repro.core.api).
    """
    res = merge_mod.upsert(
        state, cfg, keys, pad_rows(values, cfg), custom_scores=custom_scores,
        stages=_upsert_stages(backend, cfg),
    )
    if telemetry is not None:
        telemetry.record(
            "insert_or_assign",
            _obs().observe_upsert(state, cfg, keys, res.status))
    return UpsertResult(state=res.state, status=res.status)


class InsertAndEvictResult(NamedTuple):
    state: HKVState
    status: jax.Array
    evicted: EvictionStream   # positionally aligned with the input batch


@roles.inserter
def insert_and_evict(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    custom_scores: Optional[U64] = None,
    *,
    backend: str = "auto",
    loc: Optional[find_mod.Locate] = None,
    telemetry=None,
) -> InsertAndEvictResult:
    """Inserter. insert_or_assign that returns the displaced entries in the
    same launch as a typed `EvictionStream` (the paper's single-kernel
    eviction hand-off — the transport the tier hierarchy's demotion cascade
    rides on; see `core/tiered.py`).  `loc` is the probe-sharing seam: a
    caller that just located the same batch passes it through to the
    closure (see `merge.upsert`)."""
    res = merge_mod.upsert(
        state,
        cfg,
        keys,
        pad_rows(values, cfg),
        custom_scores=custom_scores,
        return_evicted=True,
        stages=_upsert_stages(backend, cfg),
        loc=loc,
    )
    if telemetry is not None:
        telemetry.record(
            "insert_and_evict",
            _obs().observe_upsert(state, cfg, keys, res.status))
    return InsertAndEvictResult(state=res.state, status=res.status,
                                evicted=res.evicted)


class FindOrInsertResult(NamedTuple):
    state: HKVState
    values: jax.Array   # [N, dim] — existing value on hit, init value on admit/reject
    found: jax.Array    # bool [N] — key existed before this call
    status: jax.Array
    # Displaced pairs (lanes populated iff return_evicted; else the
    # zero-length placeholder) — lets a cold-start admit double as the
    # hot tier's demotion source in `core/tiered.py`.
    evicted: EvictionStream


@roles.inserter
def find_or_insert(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    init_values: jax.Array,
    custom_scores: Optional[U64] = None,
    *,
    backend: str = "auto",
    return_evicted: bool = False,
    loc: Optional[find_mod.Locate] = None,
    telemetry=None,
) -> FindOrInsertResult:
    """Inserter. Lookup; insert `init_values` for missing keys (cold-start).

    Hits keep their stored value (scores touched per policy); misses insert
    subject to admission control.  Returned rows: stored value for every key
    now present; the caller's init row for keys whose admission was rejected
    (an *ephemeral* value — the paper returns the same from its workspace).

    Probe cost: ONE probe pass (ZERO when the caller supplies `loc`).  The
    closure publishes every key's post-op location (`MergeResult.loc`), so
    the value readback is a position-addressed gather — no pre- or
    post-locate (the seams that used to cost two extra passes; pinned by
    tests/test_upsert_kernel.py).

    Consumer code: prefer `HKVTable.find_or_insert` (repro.core.api).
    """
    res = merge_mod.upsert(
        state,
        cfg,
        keys,
        pad_rows(init_values, cfg),
        custom_scores=custom_scores,
        write_hit_values=False,
        return_evicted=return_evicted,
        stages=_upsert_stages(backend, cfg),
        loc=loc,
    )
    vals = _gather_post(res, cfg, init_values, backend)
    if telemetry is not None:
        telemetry.record(
            "find_or_insert",
            _obs().observe_upsert(state, cfg, keys, res.status,
                                  found=res.found))
    return FindOrInsertResult(state=res.state, values=vals, found=res.found,
                              status=res.status, evicted=res.evicted)


def _gather_post(res: MergeResult, cfg: HKVConfig, init_values: jax.Array,
                 backend: str) -> jax.Array:
    """Value readback at the closure-published post-op locations; rejected
    keys fall back to the caller's init row (ephemeral)."""
    if _resolve_backend(backend) == "kernel" and cfg.value_tier == "hbm":
        from repro.kernels import ops as kernel_ops

        vals = kernel_ops.gather_rows_kernel(res.state, cfg, res.loc, cfg.dim)
    else:
        vals = find_mod.gather_values(res.state, res.loc, cfg.dim, cfg)
    return jnp.where(res.loc.found[:, None], vals, init_values[:, : cfg.dim])


@roles.inserter
def accum_or_assign(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    values: jax.Array,
    custom_scores: Optional[U64] = None,
    *,
    telemetry=None,
) -> UpsertResult:
    """Inserter. Paper API: ACCUMULATE into existing entries (+=), ASSIGN new
    ones — the one-shot gradient-accumulation upsert.

    Batch semantics: duplicates of a key within the batch are pre-summed,
    then a single += applies on hit (or the sum is inserted on miss,
    admission-controlled)."""
    n = keys.hi.shape[0]
    d = merge_mod.dedupe_keys(keys)
    v = pad_rows(values, cfg)
    v_sum = jax.ops.segment_sum(v[d.idx_sorted], d.gid, num_segments=n)[d.gid]
    # phase 1: += on existing keys (updater-style, but score-touching)
    state2 = assign_add(state, cfg, d.unique, v_sum)
    # phase 2: structural insert of the remaining misses with the summed value
    cs = None
    if custom_scores is not None:
        # last-writer-wins on duplicate lanes' customs, matching the
        # insert_or_assign convention: each sorted slot takes its group's
        # LAST original occurrence (idx_sorted would take the first)
        cs = U64(custom_scores.hi[d.last_index], custom_scores.lo[d.last_index])
    res = merge_mod.upsert(
        state2, cfg, d.unique, v_sum, custom_scores=cs, write_hit_values=False
    )
    # res.status is in unique's (key-sorted, deduped) order: only each
    # group's representative slot carries the group status (the masked
    # duplicates are INVALID) — d.inverse maps every original position to
    # its group's representative slot.
    status = res.status[d.inverse]
    if telemetry is not None:
        telemetry.record(
            "accum_or_assign",
            _obs().observe_upsert(state, cfg, keys, status))
    return UpsertResult(state=res.state, status=status)


@roles.inserter
def ingest(
    state: HKVState,
    cfg: HKVConfig,
    keys: U64,
    init_values: jax.Array,
    custom_scores: Optional[U64] = None,
    *,
    backend: str = "auto",
    telemetry=None,
) -> UpsertResult:
    """Inserter. Admission-only upsert: misses insert `init_values`
    (admission-controlled), hits keep their stored value with scores
    touched per policy — find_or_insert without the value readback (the
    deferred-structural overlapped-ingest schedule, §3.5/Exp#3e)."""
    res = merge_mod.upsert(
        state, cfg, keys, pad_rows(init_values, cfg),
        custom_scores=custom_scores, write_hit_values=False,
        stages=_upsert_stages(backend, cfg),
    )
    if telemetry is not None:
        telemetry.record(
            "ingest", _obs().observe_upsert(state, cfg, keys, res.status,
                                            found=res.found))
    return UpsertResult(state=res.state, status=res.status)


@roles.inserter
def erase(state: HKVState, cfg: HKVConfig, keys: U64, *,
          telemetry=None) -> HKVState:
    """Inserter (structural). Remove keys; freed slots return to the pool."""
    loc = find_mod.locate(state, cfg, keys)
    if telemetry is not None:
        telemetry.record(
            "erase", _obs().observe_erase(state, cfg, keys, loc.found))
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    hb = jnp.where(loc.found, loc.bucket, b)
    row = jnp.where(loc.found, loc.row, b * s)
    n = keys.hi.shape[0]
    return state._replace(
        key_hi=state.key_hi.at[hb, loc.slot].set(jnp.full((n,), u64.EMPTY_HI), mode="drop"),
        key_lo=state.key_lo.at[hb, loc.slot].set(jnp.full((n,), u64.EMPTY_LO), mode="drop"),
        digests=state.digests.at[hb, loc.slot].set(
            jnp.full((n,), u64.EMPTY_DIGEST), mode="drop"
        ),
        score_hi=state.score_hi.at[hb, loc.slot].set(jnp.zeros((n,), jnp.uint32), mode="drop"),
        score_lo=state.score_lo.at[hb, loc.slot].set(jnp.zeros((n,), jnp.uint32), mode="drop"),
        values=table_mod.tier_scatter(
            cfg.value_tier, state.values, row,
            jnp.zeros((n, cfg.total_value_dim), state.values.dtype),
        ),
    )


@roles.inserter
def clear(state: HKVState, cfg: HKVConfig) -> HKVState:
    """Inserter (structural). Drop every entry."""
    return table_mod.create(cfg)._replace(
        clock_hi=state.clock_hi, clock_lo=state.clock_lo, epoch=state.epoch
    )


# =============================================================================
# Predicated sweeps (the maintenance subsystem's bulk ops — DESIGN.md
# §Maintenance).  These run BETWEEN serving waves, not inside upserts:
# whole-table passes over the metadata planes, driven by a declarative
# `SweepPredicate` (core/predicates.py) so they compile under jit and
# evaluate identically on both backends (the kernel path accelerates the
# mask stage; everything downstream is shared orchestration, the
# UpsertStages pattern).
# =============================================================================


class SweepResult(NamedTuple):
    state: HKVState
    swept: jax.Array     # int32 [] — entries removed by this sweep


class EvictIfResult(NamedTuple):
    state: HKVState
    # Rank-aligned (NOT batch-aligned) stream: lane i carries the i-th
    # coldest matching entry (score asc, then key asc — a total order),
    # mask False beyond the matched/limit count.  Same transport type as
    # the upsert eviction hand-off, so the tier hierarchy demotes it
    # through the identical cascade (`TieredHKVTable.demote`).
    evicted: EvictionStream
    count: jax.Array     # int32 [] — live lanes in the stream


def _sweep_mask(state: HKVState, cfg: HKVConfig, pred,
                backend: str) -> jax.Array:
    """bool [B, S] — live entries matching `pred` (the one stage the
    Pallas sweep kernel replaces; both backends evaluate the same
    `match_planes` formula, so the masks are bit-identical)."""
    if _resolve_backend(backend) == "kernel":
        from repro.kernels import ops as kernel_ops  # deferred: kernels import core

        return kernel_ops.sweep_mask_kernel(state, cfg, pred)
    return pred.matches(state.keys, state.scores) & state.occupied_mask()


def _erase_slots(state: HKVState, cfg: HKVConfig, mask: jax.Array) -> HKVState:
    """Clear every slot where mask [B, S] is True: keys/digests to the
    EMPTY sentinels, scores to 0, value rows zeroed (via the tier-aware
    masked row clear, honoring the §3.6 crossing contract)."""
    return state._replace(
        key_hi=jnp.where(mask, jnp.uint32(u64.EMPTY_HI), state.key_hi),
        key_lo=jnp.where(mask, jnp.uint32(u64.EMPTY_LO), state.key_lo),
        digests=jnp.where(mask, jnp.uint8(u64.EMPTY_DIGEST), state.digests),
        score_hi=jnp.where(mask, jnp.uint32(0), state.score_hi),
        score_lo=jnp.where(mask, jnp.uint32(0), state.score_lo),
        values=table_mod.tier_mask_rows(cfg.value_tier, state.values,
                                        ~mask.reshape(-1),
                                        cfg.total_value_dim),
    )


@roles.inserter
def erase_if(state: HKVState, cfg: HKVConfig, pred, *,
             backend: str = "auto", telemetry=None) -> SweepResult:
    """Inserter (structural). Remove EVERY live entry matching `pred` —
    the paper-family `erase_if` bulk op (TTL/epoch expiry rides on this
    with the `expire_before` canned predicate).

    Consumer code: prefer `HKVTable.erase_if` (repro.core.api).
    """
    mask = _sweep_mask(state, cfg, pred, backend)
    swept = jnp.sum(mask.astype(jnp.int32))
    if telemetry is not None:
        telemetry.record("erase_if", _obs().observe_sweep(cfg, swept))
    return SweepResult(state=_erase_slots(state, cfg, mask), swept=swept)


@roles.inserter
def evict_if(state: HKVState, cfg: HKVConfig, pred, budget: int, *,
             limit: Optional[jax.Array] = None,
             backend: str = "auto", telemetry=None) -> EvictIfResult:
    """Inserter (structural). Remove up to `budget` matching entries,
    COLDEST FIRST (ascending score, ties by ascending key — deterministic
    and backend-independent), and hand them back as an `EvictionStream`.

    This is the maintenance primitive behind proactive tier rebalancing:
    the hierarchy evicts the coldest hot-tier entries here and demotes
    the returned stream into the cold tier, so the serving path's
    reactive upsert evictions become rare (DESIGN.md §Maintenance).

    `budget` is static (the stream's lane count, clamped to the table's
    capacity — so the protocol surface accepts whole-hierarchy budgets
    uniformly across impls); `limit` is an optional DYNAMIC cap <=
    budget — lanes at rank >= limit stay resident (the watermark
    rebalancer computes the needed move count at trace time).
    """
    b, s = cfg.num_buckets, cfg.slots_per_bucket
    c = b * s
    if budget < 1:
        raise ValueError(f"budget must be >= 1; got {budget}")
    budget = min(budget, c)
    mask = _sweep_mask(state, cfg, pred, backend)
    flat = mask.reshape(-1)
    # candidates first, ordered coldest-first: sort by (non-candidate,
    # score, key); keys are unique table-wide, so the order is total
    row = u64.lexsort(((~flat).astype(jnp.uint32),
                       state.score_hi.reshape(-1), state.score_lo.reshape(-1),
                       state.key_hi.reshape(-1), state.key_lo.reshape(-1)))
    row_t = row[:budget]
    lane = flat[row_t]
    if limit is not None:
        lane &= jnp.arange(budget, dtype=jnp.int32) < limit
    bkt = row_t // s
    slot = row_t % s
    khi = state.key_hi[bkt, slot]
    klo = state.key_lo[bkt, slot]
    vals = table_mod.tier_gather(cfg.value_tier, state.values,
                                 jnp.where(lane, row_t, 0),
                                 cfg.total_value_dim)
    vals = jnp.where(lane[:, None], vals, jnp.zeros_like(vals))
    stream = EvictionStream(
        key_hi=jnp.where(lane, khi, 0),
        key_lo=jnp.where(lane, klo, 0),
        values=vals,
        score_hi=jnp.where(lane, state.score_hi[bkt, slot], 0),
        score_lo=jnp.where(lane, state.score_lo[bkt, slot], 0),
        mask=lane,
    )
    # erase the evicted slots (OOB-drop the masked-out lanes)
    eb = jnp.where(lane, bkt, b)
    nlanes = budget
    state = state._replace(
        key_hi=state.key_hi.at[eb, slot].set(
            jnp.full((nlanes,), u64.EMPTY_HI), mode="drop"),
        key_lo=state.key_lo.at[eb, slot].set(
            jnp.full((nlanes,), u64.EMPTY_LO), mode="drop"),
        digests=state.digests.at[eb, slot].set(
            jnp.full((nlanes,), u64.EMPTY_DIGEST), mode="drop"),
        score_hi=state.score_hi.at[eb, slot].set(
            jnp.zeros((nlanes,), jnp.uint32), mode="drop"),
        score_lo=state.score_lo.at[eb, slot].set(
            jnp.zeros((nlanes,), jnp.uint32), mode="drop"),
        values=table_mod.tier_scatter(
            cfg.value_tier, state.values, jnp.where(lane, row_t, c),
            jnp.zeros((nlanes, cfg.total_value_dim), state.values.dtype),
        ),
    )
    count = jnp.sum(lane.astype(jnp.int32))
    if telemetry is not None:
        telemetry.record("evict_if", _obs().observe_evict_if(cfg, count))
    return EvictIfResult(state=state, evicted=stream, count=count)


# =============================================================================
# helpers
# =============================================================================


def pad_rows(values: jax.Array, cfg: HKVConfig) -> jax.Array:
    """Zero-pad caller rows up to the logical row width (aux optimizer
    cols) — the ONE padding/dtype point every row-writing path shares
    (flat ops here, the tier hierarchy in `core/tiered.py`)."""
    vdim = cfg.total_value_dim
    if values.shape[1] == vdim:
        return values.astype(cfg.value_dtype)
    pad = jnp.zeros((values.shape[0], vdim - values.shape[1]), cfg.value_dtype)
    return jnp.concatenate([values.astype(cfg.value_dtype), pad], axis=1)
