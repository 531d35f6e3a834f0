"""Train→serve publication: snapshot-consistent table hand-off.

The §3.5 triple-group taxonomy under REAL interleave: an online trainer
(updater + inserter roles) keeps mutating its working table while the
serving engine (reader role, `repro.serving.embedding_engine`) reads.
Handles are immutable pytrees, so publication is trivially atomic — a
single Python reference swap of a `(version, table)` tuple.  A reader
that snapshots once per wave can never observe a half-published table:
either it gets the pre-publish handle (whole) or the post-publish handle
(whole).  There is no state in between to observe.

Two publication paths:

  handle swap   same-process: `publish(table)` swaps the snapshot tuple.
                The engine's miss-path admissions flow back through
                `offer(version, table)` — a compare-and-swap that the
                trainer's own publication beats (admission effects on the
                read path are advisory; the trainer republishes promptly
                and re-admission costs one miss).  Under the engine's
                continuous admission the cadence is per DISPATCH: the
                snapshot is read when a wave launches and the successor
                is offered immediately — as a handle of still-computing
                async arrays, which is safe because handles are pytrees
                of device futures and the next wave's launch chains on
                them through ordinary data dependencies.
  delta export  cross-process: `export_delta(table)` drains the table
                through `export_batch` into a picklable numpy
                `TableDelta`; `ingest_delta(table, delta)` replays it via
                `ingest` (admission-controlled, scores carried as custom
                where the destination policy accepts them).  This is the
                multi-host publish seam — the transport (files, RPC) is
                the caller's.

`OnlineTrainer` is the reference updater: find_or_insert admission (the
step's single structural op) + a fused read-modify-write session
(`update_rows`, ONE shared locate) per gradient batch, publishing every
`publish_every` steps.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import u64
from repro.core.api import normalize_keys
from repro.core.table import jit_returning_table
from repro.core.u64 import U64
from repro.obs.telemetry import TelemetrySink
from repro.obs.trace import as_tracer


# =============================================================================
# Table sources — what the engine reads from
# =============================================================================


@runtime_checkable
class TableSource(Protocol):
    """Wave-granular table supply: `snapshot()` returns `(version, table)`
    atomically; `offer(version, table)` hands a read-path successor back
    (admission/promotion effects), applied only if `version` is still
    current."""

    def snapshot(self) -> tuple: ...

    def offer(self, version: int, table: Any) -> bool: ...


class StaticSource:
    """Engine-owned source (no trainer) with the SAME compare-and-swap
    offer contract as `TablePublisher`: an offer only applies when the
    offerer's snapshot version is still current, and the new version bumps
    from the CURRENT snapshot — never from the caller's argument.  Even
    without a trainer, two offer paths race here (the engine's wave
    admissions and the maintenance scheduler's between-wave steps), and a
    stale offer must lose rather than silently clobber a newer table or
    reuse a version number."""

    def __init__(self, table: Any):
        self._snap = (0, table)
        self.offered = 0             # offers accepted
        self.rejected_offers = 0     # offers beaten by a newer successor

    def snapshot(self) -> tuple:
        return self._snap

    def offer(self, version: int, table: Any) -> bool:
        if self._snap[0] != version:
            self.rejected_offers += 1
            return False
        self._snap = (self._snap[0] + 1, table)
        self.offered += 1
        return True

    @property
    def table(self) -> Any:
        return self._snap[1]


class TablePublisher:
    """The train→serve hand-off point.

    The trainer calls `publish(table)`; the engine calls `snapshot()` once
    per wave and `offer(...)` when its own policy mutated the table.  The
    snapshot tuple is swapped under a lock (offers need compare-and-swap);
    readers are lock-free — tuple read is atomic under the GIL and the
    tuple itself is immutable.
    """

    def __init__(self, table: Any, *, tracer: Optional[Any] = None):
        self._snap = (0, table)
        self._lock = threading.Lock()
        self.published = 0           # trainer publications
        self.offered = 0             # engine offers accepted
        self.rejected_offers = 0     # engine offers beaten by a publish
        # span tracing: publisher.publish / publisher.offer instants
        # (repro.obs.trace; noop when unwired)
        self.tracer = as_tracer(tracer)

    def snapshot(self) -> tuple:
        return self._snap

    @property
    def version(self) -> int:
        return self._snap[0]

    @property
    def table(self) -> Any:
        return self._snap[1]

    def publish(self, table: Any) -> int:
        """Unconditional swap (the trainer wins races); returns the new
        version."""
        with self._lock:
            v = self._snap[0] + 1
            self._snap = (v, table)
            self.published += 1
        self.tracer.instant("publisher.publish", version=v)
        return v

    def offer(self, version: int, table: Any) -> bool:
        """Compare-and-swap from the read path: applies only if the reader's
        snapshot is still current (a concurrent `publish` supersedes the
        offered admission effects — they are advisory; see module doc)."""
        with self._lock:
            if self._snap[0] != version:
                self.rejected_offers += 1
                accepted = False
            else:
                self._snap = (version + 1, table)
                self.offered += 1
                accepted = True
        self.tracer.instant("publisher.offer", version=version,
                            accepted=accepted)
        return accepted


# =============================================================================
# The delta path — export_batch → ingest, cross-process publishable
# =============================================================================


class TableDelta(NamedTuple):
    """Host-side (numpy, picklable) live-entry dump of a table."""

    keys: np.ndarray     # uint64 [n]
    values: np.ndarray   # float32 [n, total_value_dim]
    scores: np.ndarray   # uint64 [n]

    @property
    def count(self) -> int:
        return int(self.keys.shape[0])


def export_delta(table: Any, *, chunk_buckets: int = 64,
                 tracer: Optional[Any] = None) -> TableDelta:
    """Drain a table's live entries through `export_batch` in
    `chunk_buckets`-bucket chunks (any handle exposing
    `num_buckets`/`export_batch`: flat, tiered — whose concatenated bucket
    space dedupes inclusive copies — or the dict baselines)."""
    with as_tracer(tracer).span("delta.export"):
        return _export_delta(table, chunk_buckets=chunk_buckets)


def _export_delta(table: Any, *, chunk_buckets: int) -> TableDelta:
    ks, vs, ss = [], [], []
    nb = table.num_buckets
    for start in range(0, nb, chunk_buckets):
        exp = table.export_batch(start, min(chunk_buckets, nb - start))
        mask = np.asarray(exp.mask)
        if not mask.any():
            continue
        hi = np.asarray(exp.key_hi, np.uint64)[mask]
        lo = np.asarray(exp.key_lo, np.uint64)[mask]
        shi = np.asarray(exp.score_hi, np.uint64)[mask]
        slo = np.asarray(exp.score_lo, np.uint64)[mask]
        ks.append((hi << np.uint64(32)) | lo)
        ss.append((shi << np.uint64(32)) | slo)
        vs.append(np.asarray(exp.values)[mask])
    if not ks:
        width = getattr(table, "dim", 0)
        return TableDelta(keys=np.zeros(0, np.uint64),
                          values=np.zeros((0, width), np.float32),
                          scores=np.zeros(0, np.uint64))
    return TableDelta(keys=np.concatenate(ks),
                      values=np.concatenate(vs).astype(np.float32),
                      scores=np.concatenate(ss))


def ingest_delta(table: Any, delta: TableDelta, *, batch: int = 1024,
                 carry_scores: bool = False,
                 tracer: Optional[Any] = None,
                 telemetry: Optional[Any] = None) -> Any:
    """Replay a delta into any inserter-capable handle via `ingest`
    (admission-controlled: the destination's cache semantics decide what
    sticks — the cross-process analogue of the demotion cascade's
    boundary).  `carry_scores=True` forwards the exported scores as custom
    scores; only meaningful when the destination runs the 'custom' policy
    (other policies stamp their own, `translate_scores` semantics).
    `telemetry=` threads the device counter sink through every replayed
    `ingest` call (the op-telemetry seam, DESIGN.md §Observability)."""
    dim = delta.values.shape[1] if delta.values.ndim == 2 else 0
    with as_tracer(tracer).span("delta.ingest", count=delta.count):
        for start in range(0, delta.count, batch):
            kb = delta.keys[start:start + batch]
            vb = delta.values[start:start + batch]
            sb = delta.scores[start:start + batch]
            if len(kb) < batch:   # constant shapes: one jit entry per delta
                pad = batch - len(kb)
                kb = np.concatenate([kb, np.full(pad, _EMPTY_KEY, np.uint64)])
                vb = np.concatenate([vb, np.zeros((pad, dim), vb.dtype)])
                sb = np.concatenate([sb, np.zeros(pad, np.uint64)])
            kw = {}
            if carry_scores:
                kw["custom_scores"] = u64.from_uint64(sb)
            if telemetry is not None:
                kw["telemetry"] = telemetry
            res = table.ingest(u64.from_uint64(kb), jnp.asarray(vb), **kw)
            table = res.table
    return table


# =============================================================================
# OnlineTrainer — the reference updater/inserter loop
# =============================================================================


@dataclasses.dataclass
class OnlineTrainer:
    """Streaming trainer against a private successor chain, publishing
    whole handles.

    One `train_step(keys, grads)`:
      1. `find_or_insert` admits the step's keys (INSERTER — the single
         structural op; on a tiered table this also promotes cold hits);
      2. a session `update_rows` applies `update_fn(rows, grads)` over the
         same key batch (UPDATER — fused gather+write-back, one locate);
      3. every `publish_every` steps the successor handle is published.

    `update_fn(rows, grads) -> rows` sees full-width rows [n, dim+aux];
    the default is plain SGD on the embedding columns.

    `telemetry=` (a `repro.obs.telemetry.TelemetrySink`) accumulates the
    admission op's device counters across steps — the trainer-side half
    of the op-telemetry story (the update half runs through a session,
    which is out of the telemetry seam's scope).

    `donate=True` lets a step consume the trainer's table once the
    publisher no longer serves it (an engine offer or a later publish
    replaced it): the step then updates it in place instead of writing a
    whole new table.  The table the trainer starts from is consumed too,
    so a caller hands it over.
    """

    publisher: TablePublisher
    publish_every: int = 1
    lr: float = 0.1
    update_fn: Optional[Callable] = None
    steps: int = 0
    telemetry: Optional[Any] = None
    donate: bool = False

    def __post_init__(self):
        self._table = self.publisher.table
        # one compiled program per step: admission + update; the second
        # consumes the table it is given
        self._steps = (
            jit_returning_table(self._step_body, static_argnums=3),
            jit_returning_table(self._step_body, static_argnums=3,
                                donate_argnums=(0,)))

    @property
    def table(self) -> Any:
        return self._table

    def _step_body(self, t: Any, keys: U64, grads: jax.Array,
                   with_telemetry: bool):
        sink = TelemetrySink() if with_telemetry else None
        dim = grads.shape[1]
        init = jnp.zeros((grads.shape[0], dim), jnp.float32)
        if sink is not None:
            res = t.find_or_insert(keys, init, telemetry=sink)
        else:
            res = t.find_or_insert(keys, init)
        t = res.table
        fn = self.update_fn or (
            lambda rows, g: rows.at[:, :dim].add(-self.lr * g))
        s = t.session()
        s.update_rows(keys, lambda rows: fn(rows, grads))
        return s.commit(), ({} if sink is None else sink.by_op)

    def train_step(self, keys: Any, grads: jax.Array) -> Any:
        donate = self.donate and self.publisher.table is not self._table
        t, by_op = self._steps[donate](self._table, normalize_keys(keys),
                                       grads, self.telemetry is not None)
        for op, tel in by_op.items():
            self.telemetry.record(op, tel)
        self._table = t
        self.steps += 1
        if self.steps % self.publish_every == 0:
            self.publish()
        return t

    def publish(self) -> int:
        """Swap the trainer's current successor in as the served table."""
        return self.publisher.publish(self._table)


_EMPTY_KEY = u64.EMPTY_KEY
