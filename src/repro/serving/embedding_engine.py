"""OnlineEmbeddingEngine — the paper's title scenario as a serving loop.

Continuous online embedding storage (§1, Fig. 1) means a table that is
read under heavy traffic WHILE an online trainer keeps ingesting and
updating — the read-heavy regime the abstract's headline numbers describe
(3.9 B-KV/s `find`, stable across load factors).  This engine is that
read path, built over ANY `KVTable` handle:

  * `HKVTable` (jnp or kernel backend) — the flat cache-semantic table;
  * `TieredHKVTable` — hot-HBM/cold-hmem hierarchy (DESIGN.md §2.5);
  * `ShardedHKVTable` — the same contract over a device mesh;
  * `DictKVTable` — the dictionary-semantic baselines, for A/B runs.

Admission comes in two modes (`admission=`):

  'wave'        wave-granular (the original contract): requests queue
                whole; each `step()` packs up to `wave_size` key lanes
                (EMPTY-padded), launches, BLOCKS, and unpacks — one
                serial cycle per wave.
  'continuous'  continuous batching: ADMISSION IS DECOUPLED FROM THE
                SERVING CYCLE.  A persistent staging buffer with per-lane
                occupancy tracking splices arriving requests into the
                partially-drained staging wave at `submit()` time, and
                every time the buffer FILLS, the wave dispatches RIGHT
                THERE — asynchronously, without waiting for the engine's
                next `step()` — so a burst's waves queue back-to-back on
                the device instead of one per serving cycle.  The
                host↔device path is double-buffered through a deque of
                in-flight waves: key-packs and result-unpacks happen in
                the async-dispatch gap before `block_until_ready`
                (`poll()` reaps finished waves without blocking; `step()`
                flushes the partial staging wave and reaps).  Handle
                chaining is safe: each wave snapshots the (possibly not
                yet ready) successor the previous wave offered at
                dispatch; XLA orders the launches through the data
                dependency.  Under shallow load the pipeline collapses —
                a lone in-flight wave with nothing staged behind it is
                block-retired in the same step, so light traffic pays
                wave-granular latency and only bursts pipeline.

In both modes every wave is one jit cache entry; a request larger than a
wave spans several, zero-length requests complete without a launch.

Miss policy (the §3.5 role the read path plays):

  'readonly'  the wave runs `find` — READER role.  Misses return the
              engine's default row (zeros or a caller hook).  On tiered /
              sharded-tiered tables the `promote` flag threads through to
              `find(promote=...)`: promotion re-admits cold hits into the
              hot tier (structural motion on the read path — the
              inclusive-on-access cache), while `promote=False` keeps the
              wave a pure reader.
  'admit'     the wave runs `find_or_insert` — INSERTER role: misses are
              admitted (with the default row as init), so a re-accessed
              key is a hit from its second wave on.  This is the serving
              half of continuous ingestion; at λ=1.0 admission evicts
              low-score entries in place.

Served rows are exactly `table.dim` wide under BOTH policies: tables
carrying in-row optimizer state (`aux_value_dim > 0`,
`core/table.py::total_value_dim`) never leak aux columns to clients.

Tables are drawn from a `TableSource` (see `repro.serving.publisher`) at
DISPATCH granularity: each wave reads the source once when it launches
and — when the policy mutated the table (admission / promotion) —
publishes the successor back immediately, so under overlapped staging
the next dispatch chains on the offered (async) handle.  A
snapshot-consistent trainer publishes whole handles; a wave therefore
never observes a half-published table (DESIGN.md §Serving).  The cached
wave closure is keyed on the published table's static signature
(type / backend / dims / score policy): a trainer that publishes a
structurally different successor (flat→tiered retier, backend flip, dim
change) gets a freshly built closure instead of stale static flags.

Metrics split queue-wait from service per REQUEST, on top of the
per-wave numbers:

  queue-wait   submit → dispatch of the first wave carrying the request;
  service      that dispatch → results unpacked into the request;
  total        submit → done (== queue-wait + service).

`metrics()` aggregates per-wave hit rate / keys/s / p50-p99 wave latency
plus the per-request p50/p99 of all three latency components.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import u64
from repro.core.api import table_signature
from repro.core.table import jit_returning_table
from repro.core.tiered import TieredHKVTable
from repro.core.u64 import U64
from repro.obs.trace import as_tracer
from repro.serving.publisher import StaticSource, TableSource

MISS_POLICIES = ("readonly", "admit")
ADMISSION_MODES = ("wave", "continuous")


# =============================================================================
# Requests and metrics
# =============================================================================


@dataclasses.dataclass
class EmbeddingRequest:
    """One lookup request: a batch of feature ids awaiting embedding rows."""

    rid: int
    keys: np.ndarray                    # uint64 [n] feature ids
    values: Optional[np.ndarray] = None  # float32 [n, dim] — filled on completion
    found: Optional[np.ndarray] = None   # bool [n]
    done: bool = False
    # SLO accounting (host perf_counter stamps; see module doc)
    t_submit: Optional[float] = None     # stamped by engine.submit()
    t_admit: Optional[float] = None      # dispatch of the first carrying wave
    t_done: Optional[float] = None       # last carrying wave unpacked

    @property
    def queue_wait_s(self) -> float:
        """Time spent queued before the first carrying wave dispatched."""
        if self.t_submit is None or self.t_admit is None:
            return 0.0
        return self.t_admit - self.t_submit

    @property
    def service_s(self) -> float:
        """First dispatch → results unpacked (device + in-flight overlap)."""
        if self.t_admit is None or self.t_done is None:
            return 0.0
        return self.t_done - self.t_admit

    @property
    def total_latency_s(self) -> float:
        """submit → done == queue-wait + service."""
        if self.t_submit is None or self.t_done is None:
            return 0.0
        return self.t_done - self.t_submit


class WaveReport(NamedTuple):
    size: int           # live key lanes served (padding excluded)
    hits: int
    latency_s: float    # host wall clock: dispatch → results ready
    table_version: int  # publisher version the wave was served from
    hot_hits: int = 0   # lanes served from the HOT tier (tiered readonly
                        # waves; == hits elsewhere)
    demotions: int = 0  # REACTIVE hot->cold demotions this wave's own
                        # structural motion caused (tiered admission /
                        # promotion) — the serving-path eviction tax the
                        # maintenance scheduler's proactive rebalancing
                        # exists to drive toward zero (DESIGN.md
                        # §Maintenance)

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.size, 1)

    @property
    def kv_per_s(self) -> float:
        return self.size / max(self.latency_s, 1e-12)


class EngineMetrics(NamedTuple):
    waves: int
    keys: int
    hits: int
    hit_rate: float
    hot_rate: float     # hot-tier serve fraction (== hit_rate off-tier)
    kv_per_s: float     # total keys / total wave wall-clock
    p50_latency_s: float
    p99_latency_s: float
    # reactive serving-path demotions, total and per wave (tiered tables;
    # 0 elsewhere) — the number exp7's scheduler-on/off comparison pins
    reactive_demotions: int = 0
    demotions_per_wave: float = 0.0
    # per-REQUEST SLO split (completed requests; module doc):
    requests: int = 0
    p50_queue_wait_s: float = 0.0
    p99_queue_wait_s: float = 0.0
    p50_service_s: float = 0.0
    p99_service_s: float = 0.0
    p50_total_s: float = 0.0
    p99_total_s: float = 0.0

    @classmethod
    def zero(cls) -> "EngineMetrics":
        """The well-formed empty snapshot (no waves, no requests) —
        field-safe against the NamedTuple growing, unlike a positional
        zero literal."""
        return cls(waves=0, keys=0, hits=0, hit_rate=0.0, hot_rate=0.0,
                   kv_per_s=0.0, p50_latency_s=0.0, p99_latency_s=0.0)


class _Inflight(NamedTuple):
    """A dispatched, not-yet-retired wave (continuous mode holds one)."""

    out: tuple          # (vals, found, hot, dem) — async device values; the
                        # successor table lives in the source, not here
    segments: list      # (request, key offset, lane0, take)
    used: int
    lanes: np.ndarray
    version: int
    t_dispatch: float


# =============================================================================
# The engine
# =============================================================================


class OnlineEmbeddingEngine:
    """Wave-batched embedding lookups over any `KVTable` handle.

        table = TieredHKVTable.create(hot_capacity=8*128,
                                      cold_capacity=64*128, dim=16)
        eng = OnlineEmbeddingEngine(table, wave_size=512,
                                    miss_policy="admit",
                                    admission="continuous")
        eng.submit(EmbeddingRequest(rid=0, keys=ids))
        eng.run_until_drained()
        print(eng.metrics())

    `table=` may instead be a `TableSource` (e.g. `TablePublisher`), in
    which case every wave serves from the source's latest published
    handle — the train→serve coupling.  `default_row(keys_u64) -> [n,dim]`
    overrides the zero miss-fallback and the admit policy's init rows —
    except on SHARDED tables, whose admit path recomputes init rows
    owner-side from the key (caller rows are not routed); there the hook
    covers only the readonly fallback.

    `host_budget_s` is the between-wave slack budget staging and
    maintenance COMPETE for (ROADMAP): the host time this step spent
    packing/unpacking is charged against it and only the remainder is
    offered to the scheduler, which defers its step when its estimated
    cost exceeds the remaining slack.  `None` (default) leaves the
    scheduler cadence-only (the pre-continuous contract).

    `donate=True` lets a wave consume its input table when the input is
    the successor this engine itself offered and the source still serves
    it: such a handle has no other holder, so the wave updates it in
    place instead of writing a whole new table (on a tiered table, a
    whole new host-resident value plane).  A snapshot taken from the
    source is then valid only until the next wave.
    """

    def __init__(self, table: Any, *, wave_size: int,
                 miss_policy: str = "readonly",
                 promote: Optional[bool] = None,
                 default_row: Optional[Callable[[U64], jax.Array]] = None,
                 scheduler: Optional[Any] = None,
                 admission: str = "wave",
                 host_budget_s: Optional[float] = None,
                 donate: bool = False,
                 tracer: Optional[Any] = None):
        if miss_policy not in MISS_POLICIES:
            raise ValueError(
                f"miss_policy {miss_policy!r}; one of {MISS_POLICIES}")
        if admission not in ADMISSION_MODES:
            raise ValueError(
                f"admission {admission!r}; one of {ADMISSION_MODES}")
        self.source: TableSource = (
            table if isinstance(table, TableSource) else StaticSource(table))
        self.wave_size = wave_size
        self.miss_policy = miss_policy
        self.promote = promote
        self.admission = admission
        self.host_budget_s = host_budget_s
        self.donate = donate
        self._owned_version = None        # the version our accepted offer made
        self._default_row = default_row
        # span tracing (repro.obs.trace): engine.submit / wave.splice /
        # wave.dispatch / wave.reap / request lifetimes.  `as_tracer`
        # normalizes None to the shared noop so call sites stay
        # unconditional.
        self.tracer = as_tracer(tracer)
        # wave-interleaved maintenance (repro.maintenance.scheduler):
        # after each wave the scheduler gets the hand-off gap — it
        # snapshots the source, runs one budgeted step, and offers the
        # successor back through the same CAS as admissions.  Maintenance
        # time is the scheduler's own metric, never wave latency.
        self.scheduler = scheduler
        self._queue: deque = deque()      # (request, key offset)
        # staging buffer: the NEXT wave, with per-lane occupancy — a
        # spanning request's remainder and fresh arrivals splice into its
        # free lanes between steps (continuous mode packs it eagerly)
        self._stage_lanes = np.full(wave_size, _EMPTY_KEY, np.uint64)
        self._stage_segments: list = []
        self._stage_used = 0
        self._stage_age = 0               # steps a partial stage has waited
        self._flights: deque = deque()    # dispatched, not yet retired
        self._wave_fn = None              # (keeping, donating) jitted pair,
        self._wave_sig = None             # rebuilt on a new table signature
        self._mutates = False             # resolved with the wave fn
        self.completed: list = []
        self.reports: list[WaveReport] = []

    # -- admission -------------------------------------------------------------

    def submit(self, req: EmbeddingRequest):
        req.values = None
        req.found = None
        req.done = False
        if req.t_submit is None:
            req.t_submit = time.perf_counter()
        self.tracer.instant("engine.submit", rid=req.rid, keys=len(req.keys))
        self._queue.append((req, 0))
        if self.admission == "continuous":
            # splice into the partially-drained staging wave right away:
            # free lanes don't wait for the next step's pack — and every
            # wave the splice FILLS dispatches immediately (async), so a
            # burst chains onto the device without waiting out the
            # serving cycle
            while True:
                self._fill_staging()
                if self._stage_used < self.wave_size:
                    break
                lanes, segments, used = self._take_staging()
                flight = self._dispatch(lanes, segments, used)
                if flight is not None:
                    self._flights.append(flight)

    @property
    def idle(self) -> bool:
        return (not self._queue and self._stage_used == 0
                and not self._stage_segments and not self._flights)

    def _fill_staging(self):
        """Move queued keys into the staging buffer's free lanes (per-lane
        occupancy: `_stage_used` is the first free lane)."""
        while self._queue and self._stage_used < self.wave_size:
            req, off = self._queue.popleft()
            take = min(len(req.keys) - off, self.wave_size - self._stage_used)
            lane0 = self._stage_used
            self._stage_lanes[lane0:lane0 + take] = req.keys[off:off + take]
            self._stage_segments.append((req, off, lane0, take))
            self._stage_used += take
            if off + take < len(req.keys):   # spans into the next wave
                self._queue.appendleft((req, off + take))
                break

    def _take_staging(self):
        """Claim the staged wave and reset the buffer for the next one."""
        with self.tracer.span("wave.splice"):
            self._fill_staging()
        lanes, segments, used = (self._stage_lanes, self._stage_segments,
                                 self._stage_used)
        self._stage_lanes = np.full(self.wave_size, _EMPTY_KEY, np.uint64)
        self._stage_segments = []
        self._stage_used = 0
        self._stage_age = 0
        return lanes, segments, used

    # -- the wave step ---------------------------------------------------------

    def _build_wave_fn(self, table):
        policy, promote = self.miss_policy, self.promote
        is_tiered = isinstance(table, TieredHKVTable)
        # late import: serving must not pull the distributed layer in for
        # single-device tables
        try:
            from repro.distributed.table_sharding import ShardedHKVTable
            is_sharded = isinstance(table, ShardedHKVTable)
        except Exception:  # pragma: no cover - distributed layer unavailable
            is_sharded = False
        default_row = self._default_row or (
            lambda k: jnp.zeros((k.hi.shape[0], table.dim), jnp.float32))
        # Does this policy mutate the table?  Static: admission always
        # does; a readonly wave only via tiered/sharded promotion.  (An
        # identity check on the jit output would not work — jit rebuilds
        # the handle object even when the state is unchanged.)
        self._mutates = (policy == "admit"
                         or (bool(promote) and (is_tiered or is_sharded)))

        zero = jnp.int32(0)

        def wave(table, kh, kl):
            k = U64(kh, kl)
            init = default_row(k)
            if policy == "admit":
                if is_sharded:
                    # owner shards recompute init rows from the key (the
                    # routed protocol: caller init is not shipped), so the
                    # returned rows ARE the stored rows — `default_row`
                    # applies only to the readonly fallback here
                    r = table.find_or_insert(k)
                else:
                    r = table.find_or_insert(k, init)
                # clients get exactly dim columns: rows on aux-carrying
                # tables (total_value_dim > dim) keep optimizer state
                # server-side
                vals = r.values[:, : table.dim]
                # reactive demotion count: what THIS wave's admissions
                # pushed hot->cold in-line (tiered handles report it)
                dem = getattr(r, "demoted", zero)
                return r.table, (vals, r.found, r.found, dem)
            # readonly: READER role — default-row fallback on miss.  Wave
            # lookups inherit the handle's backend, so kernel-backed
            # tables serve each wave with the fused find pass
            if is_tiered or is_sharded:
                r = table.find(k, promote=bool(promote))
                succ = r.table if promote else table
            else:
                r = table.find(k)
                succ = table
            vals = jnp.where(r.found[:, None], r.values[:, : table.dim], init)
            dem = getattr(r, "demoted", zero) if promote else zero
            return succ, (vals, r.found, getattr(r, "hot_hit", r.found), dem)

        if is_sharded:
            # shard_map ops jit internally; outer jit is per-mesh
            return wave, wave
        return (jit_returning_table(wave),
                jit_returning_table(wave, donate_argnums=(0,)))

    def _wave_fn_for(self, table, donate: bool):
        """The compiled wave closure for this table (the one that consumes
        its input table when `donate`), rebuilt when the
        published handle's static signature changed (type / backend /
        dims / score policy) — a trainer may retier or reshape the table
        mid-stream and the closure's baked-in flags must follow."""
        sig = table_signature(table)
        if self._wave_fn is None or sig != self._wave_sig:
            self._wave_fn = self._build_wave_fn(table)
            self._wave_sig = sig
        return self._wave_fn[donate]

    def _dispatch(self, lanes, segments, used) -> Optional[_Inflight]:
        """Launch one wave asynchronously (no block).  Zero-live waves
        (only zero-length requests) complete immediately without a
        launch."""
        version, table = self.source.snapshot()  # ONE read: wave-consistent
        if used == 0:
            now = time.perf_counter()
            for req, _off, _lane0, _take in segments:
                req.values = np.zeros((0, table.dim), np.float32)
                req.found = np.zeros(0, bool)
                req.t_admit = req.t_admit or now
                req.t_done = now
                req.done = True
                self.completed.append(req)
                self.tracer.complete_abs("request", req.t_submit, now,
                                         rid=req.rid, keys=len(req.keys))
            return None
        donate = self.donate and version == self._owned_version
        fn = self._wave_fn_for(table, donate)
        k = u64.from_uint64(lanes)
        t0 = time.perf_counter()
        with self.tracer.span("wave.dispatch", used=used, version=version):
            succ, out = fn(table, k.hi, k.lo)
            if self._mutates:     # admission / promotion built a successor;
                # offer the (possibly still computing) handle NOW so the next
                # dispatch chains on it — XLA orders launches by data deps.
                # Accepted, it is version + 1 and only the source holds it
                accepted = self.source.offer(version, succ)
                self._owned_version = version + 1 if accepted else None
        for req, _off, _lane0, _take in segments:
            if req.t_admit is None:
                req.t_admit = t0
        return _Inflight(out=out, segments=segments, used=used, lanes=lanes,
                         version=version, t_dispatch=t0)

    def _retire(self, flight: _Inflight) -> WaveReport:
        """Block on a dispatched wave, unpack results into its requests."""
        with self.tracer.span("wave.reap", used=flight.used,
                              version=flight.version):
            vals, found, hot, dem = flight.out
            vals, found, hot, dem = jax.block_until_ready(
                (vals, found, hot, dem))
            dt = time.perf_counter() - flight.t_dispatch
            vals = np.asarray(vals)
            found = np.asarray(found)
            hot = np.asarray(hot)
            now = time.perf_counter()
            for req, off, lane0, take in flight.segments:
                if req.values is None:
                    req.values = np.zeros((len(req.keys), vals.shape[1]),
                                          vals.dtype)
                    req.found = np.zeros(len(req.keys), bool)
                req.values[off:off + take] = vals[lane0:lane0 + take]
                req.found[off:off + take] = found[lane0:lane0 + take]
                if off + take == len(req.keys):
                    req.done = True
                    req.t_done = now
                    self.completed.append(req)
                    # the request's full submit→done lifetime, from the
                    # engine's own SLO stamps (raw perf_counter epoch)
                    self.tracer.complete_abs("request", req.t_submit, now,
                                             rid=req.rid, keys=len(req.keys))
        used = flight.used
        live = ~_is_empty_np(flight.lanes[:used])
        report = WaveReport(size=int(live.sum()),
                            hits=int(found[:used][live].sum()),
                            latency_s=dt, table_version=flight.version,
                            hot_hits=int(hot[:used][live].sum()),
                            demotions=int(dem))
        self.reports.append(report)
        return report

    def _maintenance_slot(self, staging_s: float):
        """The between-wave hand-off gap: staging already spent
        `staging_s` of the host budget; maintenance competes for the
        remainder (one budget — ROADMAP's slack contract)."""
        if self.scheduler is None:
            return
        slack = None
        if self.host_budget_s is not None:
            slack = max(0.0, self.host_budget_s - staging_s)
        self.scheduler.on_wave(self.source, slack_s=slack)

    def step(self) -> Optional[WaveReport]:
        """Serve one wave; returns its report.

        'wave' mode: pack → dispatch → block → unpack, serially (None
        when the queue is idle).  'continuous' mode: flush the partial
        staging wave (waves the splice filled already dispatched at
        submit), reap finished flights without blocking, and
        block-retire the oldest wave when draining or when a lone
        shallow-load wave is in flight (pipeline collapse).  The report
        may cover an earlier wave than the one dispatched this step;
        None when nothing retired (check `.idle` to drive draining, or
        use `run_until_drained`)."""
        if self.idle:
            return None
        t_host0 = time.perf_counter()
        if self.admission == "wave":
            lanes, segments, used = self._take_staging()
            flight = self._dispatch(lanes, segments, used)
            pack_s = time.perf_counter() - t_host0
            report = self._retire(flight) if flight is not None else None
            self._maintenance_slot(pack_s)
            return report
        # continuous: full waves already dispatched at submit.  The
        # PARTIAL staging wave flushes when the pipeline is SHALLOW
        # (<= 1 in flight: the device has spare capacity, so a padded
        # wave costs no one anything) or once it has waited out two
        # whole steps without filling (the straggler cap — a lone
        # request must not wait out a deep drain).  While the pipeline
        # is deep, staged keys keep accepting splices so backlog
        # traffic rides densely packed waves: EMPTY-padded lanes cost
        # full compute, and flushing every step at half fill would put
        # the device at saturation and grow the chain without bound
        flight = None
        if ((self._queue or self._stage_used or self._stage_segments)
                and (len(self._flights) <= 1 or self._stage_age >= 2)):
            lanes, segments, used = self._take_staging()
            flight = self._dispatch(lanes, segments, used)
            if flight is not None:
                self._flights.append(flight)
        elif self._stage_used or self._stage_segments:
            self._stage_age += 1
        pack_s = time.perf_counter() - t_host0
        # non-blocking reap of finished waves, in chain order
        report = None
        reaped = False
        while self._flights and _flight_ready(self._flights[0]):
            report = self._retire(self._flights.popleft())
            reaped = True
        if self._flights and flight is None and not reaped:
            # nothing dispatched, nothing ready: block on the oldest so
            # every step makes progress (the drain path)
            report = self._retire(self._flights.popleft())
        elif (flight is not None and len(self._flights) == 1
                and not self._queue and self._stage_used == 0
                and not self._stage_segments):
            # pipeline collapse: a lone shallow-load wave with nothing
            # staged behind it retires in the step it dispatched —
            # wave-granular latency instead of waiting out a reap cycle
            report = self._retire(self._flights.popleft())
        unpack_s = time.perf_counter() - t_host0 - pack_s
        self._maintenance_slot(pack_s + unpack_s)
        return report

    def poll(self) -> Optional[WaveReport]:
        """Non-blocking reap: retire every in-flight wave whose results
        are ready, without dispatching anything.  The event-loop seam for
        continuous admission — callers waiting on arrivals poll between
        submits so finished waves complete their requests at device pace
        rather than at the serving-cycle cadence.  Returns the last
        retired wave's report (None if nothing was ready)."""
        report = None
        while self._flights and _flight_ready(self._flights[0]):
            report = self._retire(self._flights.popleft())
        return report

    def run_until_drained(self, max_waves: int = 100_000) -> list:
        for _ in range(max_waves):
            self.step()
            if self.idle:
                break
        return self.completed

    # -- metrics ---------------------------------------------------------------

    def metrics(self, *, skip_warmup: bool = True) -> EngineMetrics:
        """Aggregate wave reports + per-request SLO latencies.  Counts
        (waves/keys/hits and the rates) cover EVERY wave; the timing
        aggregates (kv_per_s, wave p50/p99) skip the first wave by
        default — it pays the jit compile and would otherwise dominate
        the percentiles (`skip_warmup=False` keeps it; per-wave numbers
        incl. the compile wave stay in `self.reports`).  The per-request
        queue-wait / service / total percentiles cover every COMPLETED
        request (including warmup — queue-wait is a property of arrival
        pressure, not of compilation)."""
        if not self.reports and not self.completed:
            return EngineMetrics.zero()
        keys = sum(r.size for r in self.reports)
        hits = sum(r.hits for r in self.reports)
        demos = sum(r.demotions for r in self.reports)
        timed = (self.reports[1:] if skip_warmup and len(self.reports) > 1
                 else self.reports)
        lat = (np.array([r.latency_s for r in timed]) if timed
               else np.zeros(1))
        tkeys = sum(r.size for r in timed)
        reqs = [r for r in self.completed if r.t_done is not None]
        qw = np.array([r.queue_wait_s for r in reqs]) if reqs else np.zeros(1)
        sv = np.array([r.service_s for r in reqs]) if reqs else np.zeros(1)
        tot = (np.array([r.total_latency_s for r in reqs]) if reqs
               else np.zeros(1))
        return EngineMetrics(
            waves=len(self.reports), keys=keys, hits=hits,
            hit_rate=hits / max(keys, 1),
            hot_rate=sum(r.hot_hits for r in self.reports) / max(keys, 1),
            kv_per_s=tkeys / max(float(lat.sum()), 1e-12),
            p50_latency_s=float(np.percentile(lat, 50)),
            p99_latency_s=float(np.percentile(lat, 99)),
            reactive_demotions=demos,
            demotions_per_wave=demos / max(len(self.reports), 1),
            requests=len(reqs),
            p50_queue_wait_s=float(np.percentile(qw, 50)),
            p99_queue_wait_s=float(np.percentile(qw, 99)),
            p50_service_s=float(np.percentile(sv, 50)),
            p99_service_s=float(np.percentile(sv, 99)),
            p50_total_s=float(np.percentile(tot, 50)),
            p99_total_s=float(np.percentile(tot, 99)),
        )


_EMPTY_KEY = u64.EMPTY_KEY


def _is_empty_np(keys: np.ndarray) -> np.ndarray:
    return keys == _EMPTY_KEY


def _flight_ready(flight: _Inflight) -> bool:
    """True when a dispatched wave's device results are ready (its
    retire would not block).  Conservative on backends without
    `is_ready`: report not-ready and let the blocking paths retire."""
    try:
        return all(x.is_ready()
                   for x in jax.tree_util.tree_leaves(flight.out))
    except AttributeError:  # pragma: no cover - backend without is_ready
        return False
