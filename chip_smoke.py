"""Run the HKV store's main path once on a TPU chip and check what it returns.

    python chip_smoke.py              # one chip: the store, then serving
    python chip_smoke.py --chips 4    # four chips: the sharded table only

One process, no children.  It runs only on a TPU: elsewhere it exits
non-zero and names the missing chip.  Every table it drives must resolve to
the compiled Pallas kernels (`backend='kernel'`, `interpret=False`).

Phases (one chip):
  store   paper config A at its published size (2^27 slots, dim 8 + one
          rowwise-adagrad column, dual-bucket, LRU): seeded distinct keys
          stream in until the table is full, two more batches force
          evictions; then find / find_rows on hits and on never-inserted
          keys (bit-identical to the jnp backend), apply_grads (bit-identical
          to the optimizer applied to the rows read back), erase_if and
          evict_if with a score predicate, and a small table driven through
          the same op kinds on both backends and the plain `OracleTable`.
  serve   `repro.launch.serve`'s embedding path in-process on a
          `TieredHKVTable` at dim 64: a 2^24-slot hot tier in HBM over a
          cold tier whose value plane lives in pinned host memory (config
          D's 2^27 slots, halved to what the host holds), warm-loaded past
          the hot capacity, then continuous-admission zipf traffic with
          online training; the engine's waves must match a one-shot find
          on the table they read, and no admitted key may go missing.
Phase (four chips, `--chips 4`):
  sharded `ShardedHKVTable` at paper config B's published size (2^27 slots,
          dim 32 + one column) owner-sharded over four chips, half full;
          every written key is found with its exact row, unseen keys miss,
          and no key overflows its routing budget.

Earlier lines report what it saw; the last line is one JSON object,
{"ok": ..., "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

A_BATCH = 1 << 20       # store phase: keys per insert batch
# Serve-phase sizes.  Engine waves and trainer steps consume the tables
# they own (donation), so serving keeps two versions of the table: the
# trainer's and the served one.  Hot tier: 2^24 slots in HBM (4.6 GB a
# version at dim 64).
SERVE_HOT = 1 << 24
# Cold tier: config D's 2^27 slots, halved until two versions of its
# host-resident value plane fit the host beside what the process already
# holds and what the first host-side computation takes (8.5-9.0 GB on a
# TPU v5e host); see `serve_cold_capacity`.
SERVE_COLD_PUBLISHED = 1 << 27
HOST_BYTES = 40 << 30           # a one-chip TPU v5e host
HOST_COMPUTE_BYTES = 10 << 30
SERVE_WAVE = 1 << 16
SERVE_WAVES = 8
B_BATCH = 1 << 20       # sharded phase: keys per insert batch


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Checks:
    """Named pass/fail results; the run is ok when every one passed."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok, detail: str = "") -> bool:
        ok = bool(ok)
        log(f"check {name}: {'ok' if ok else 'FAILED'}"
            + (f" ({detail})" if detail else ""))
        if not ok:
            self.failed.append(name)
        return ok


class Timer:
    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


def compiled(fn, *args, donate=(), name=""):
    """jit + lower + compile `fn` for `args`, logging the compile time."""
    import jax

    t = Timer()
    c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    log(f"compile {name}: {t():.2f}s")
    return c


def host_rss() -> dict:
    """This process's resident and peak resident host memory, bytes."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith(("VmRSS:", "VmHWM:")):
                out[line[:5]] = int(line.split()[1]) * 1024
    return out


def memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    rss = host_rss()
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"host_rss={rss.get('VmRSS')} host_peak_rss={rss.get('VmHWM')}")


def serve_cold_capacity(dim: int) -> tuple[int, int]:
    """(cold-tier slots, host bytes free for its value planes)."""
    from repro.core.table import value_pitch

    with open("/proc/meminfo") as f:
        total = next(int(line.split()[1]) * 1024 for line in f
                     if line.startswith("MemTotal:"))
    free = min(total, HOST_BYTES) - host_rss()["VmRSS"] - HOST_COMPUTE_BYTES
    cold = SERVE_COLD_PUBLISHED
    while cold > 128 and 2 * cold * value_pitch(dim) * 4 > free:
        cold //= 2
    return cold, free


def tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


# ----------------------------------------------------------------------------
# keys and rows, made on the device from a seed
# ----------------------------------------------------------------------------

def tokens(seed: int, start, n: int):
    """Distinct non-negative int32 tokens: an affine bijection of
    [0, 2^31) applied to the indices start .. start+n-1."""
    import jax.numpy as jnp

    i = jnp.uint32(start) + jnp.arange(n, dtype=jnp.uint32)
    a = jnp.uint32(0x9E3779B1 | 1)
    c = jnp.uint32((seed * 0x85EBCA77) & 0x7FFFFFFF)
    return ((i * a + c) & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


def rows_of(tok, dim: int):
    """The row written for each token: small integers, exact in f32."""
    import jax.numpy as jnp

    t = tok.astype(jnp.uint32)[:, None]
    j = jnp.arange(dim, dtype=jnp.uint32)[None, :]
    return ((t ^ (j * jnp.uint32(0x9E37))) & jnp.uint32(0xFFFF)).astype(
        jnp.float32)


# ----------------------------------------------------------------------------
# phase: the store at paper config A
# ----------------------------------------------------------------------------

def assert_kernel_backend(check: Checks, name: str, handle) -> None:
    from repro.core import ops as ops_mod
    from repro.kernels import ops as kops

    backend = ops_mod._resolve_backend(handle.backend)
    check(f"{name} backend", backend == "kernel"
          and kops.default_interpret() is False,
          f"backend={backend} interpret={kops.default_interpret()}")


def phase_store(check: Checks, dev, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.hkv_dlrm import PAPER_CONFIGS
    from repro.core.merge import (STATUS_EVICTED, STATUS_INSERTED,
                                  STATUS_REJECTED, STATUS_UPDATED)
    from repro.core.predicates import SweepPredicate, match_planes
    from repro.kernels import ops as kops

    wall = Timer()
    emb = PAPER_CONFIGS["A"].embedding()
    t = emb.create()
    cfg = t.cfg
    jax.block_until_ready(t.state)
    assert_kernel_backend(check, "store", t)
    log(f"store: config A capacity={cfg.capacity} dim={cfg.dim} "
        f"row width={cfg.total_value_dim} value plane={t.state.values.shape} "
        f"table bytes={tree_bytes(t.state)} ({cfg.bytes_per_entry()} B/slot)")
    B = A_BATCH
    dim = cfg.dim

    def insert(t, start):
        tok = tokens(seed, start, B)
        r = t.insert_or_assign(emb.keys_of(tok), rows_of(tok, dim))
        counts = jnp.bincount(r.status.astype(jnp.int32), length=8)
        return r.table, counts, r.table.size()

    ins = compiled(insert, t, jnp.uint32(0), donate=(0,),
                   name=f"insert_or_assign batch={B}")
    counts = np.zeros(8, np.int64)
    start, batches, size = 0, 0, 0
    fill = Timer()
    limit = cfg.capacity // B + 16
    extra = 0
    while extra < 2 and batches < limit:
        t, c, sz = ins(t, jnp.uint32(start))
        start += B
        batches += 1
        size = int(sz)
        counts += np.asarray(c)
        if size == cfg.capacity:
            extra += 1
        if batches % 32 == 0:
            log(f"store: {batches} batches, size={size}, {fill():.2f}s")
    jax.block_until_ready(t.state)
    lam = size / cfg.capacity
    names = {STATUS_UPDATED: "updated", STATUS_INSERTED: "inserted",
             STATUS_EVICTED: "evicted", STATUS_REJECTED: "rejected"}
    status = {v: int(counts[k]) for k, v in names.items()}
    log(f"store: {batches} batches of {B} keys in {fill():.2f}s, "
        f"lambda={lam} size={size} status={status}; {memory_line(dev)}")
    check("store lambda=1.0", lam == 1.0, f"lambda={lam}")
    check("store evicted or rejected", status["evicted"] + status["rejected"] > 0)
    check("store statuses account for every key",
          sum(status.values()) == batches * B)

    last = start - B
    tok_hit = tokens(seed, last, B)
    tok_miss = tokens(seed, 1 << 30, B)    # indices never inserted
    jnp_t = t.with_backend("jnp")

    def readers(t, tok):
        k = emb.keys_of(tok)
        f = t.find(k)
        fr = t.find_rows(k)
        return f.values, f.found, f.score_hi, f.score_lo, fr.rows, fr.found

    rd = compiled(readers, t, tok_hit, name="find+find_rows (kernel)")
    rd_j = compiled(readers, jnp_t, tok_hit, name="find+find_rows (jnp)")
    for label, tok in (("hits", tok_hit), ("misses", tok_miss)):
        tm = Timer()
        out = jax.block_until_ready(rd(t, tok))
        log(f"store readers on {label}: {tm():.3f}s for {B} keys")
        ref = rd_j(jnp_t, tok)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(out, ref))
        check(f"store readers kernel==jnp on {label}", same)
        vals, found, _, _, rows, rfound = (np.asarray(x) for x in out)
        if label == "hits":
            want = np.asarray(rows_of(tok, dim))
            check("store find hits", found.all() and rfound.all(),
                  f"{int(found.sum())}/{B} found")
            check("store find values exact", np.array_equal(vals, want))
            check("store find_rows rows exact",
                  np.array_equal(rows[:, :dim], want)
                  and not rows[:, dim:].any())
        else:
            check("store find misses", not found.any() and not rfound.any()
                  and not vals.any(), f"{int(found.sum())}/{B} found")
    del jnp_t

    # apply_grads: the fused update kernel against the optimizer applied
    # to the rows as read back
    grads = (rows_of(tok_hit, dim) - 7.0) / 64.0
    before = rd(t, tok_hit)[4]
    want = np.asarray(emb.optimizer.apply(before, grads, dim))
    del before
    upd = compiled(lambda t, tok, g: emb.apply_grads(t, tok, g), t, tok_hit,
                   grads, donate=(0,), name="apply_grads")
    tm = Timer()
    t = upd(t, tok_hit, grads)
    jax.block_until_ready(t.state)
    log(f"store apply_grads: {tm():.3f}s for {B} keys")
    after = np.asarray(rd(t, tok_hit)[4])
    check("store apply_grads rows", np.array_equal(after, want))

    # predicated sweeps: erase everything older than the second-to-last
    # batch, then evict the coldest of what the last batch did not write
    score_of = lambda tok: int(np.asarray(rd(t, tok)[3])[0])
    s_prev = score_of(tokens(seed, last - B, B))
    s_last = score_of(tok_hit)
    st = t.state
    older = lambda thr: int(jnp.sum(
        match_planes("score_lt", st.key_hi, st.key_lo, st.score_hi,
                     st.score_lo, jnp.uint32(0), jnp.uint32(thr),
                     jnp.uint32(0), jnp.uint32(0))))
    erase_pred = SweepPredicate.score_below(s_prev)
    mask_k = kops.sweep_mask_kernel(st, cfg, erase_pred)
    mask_j = match_planes("score_lt", st.key_hi, st.key_lo, st.score_hi,
                          st.score_lo, erase_pred.a_hi, erase_pred.a_lo,
                          erase_pred.b_hi, erase_pred.b_lo)
    check("store sweep mask kernel==jnp",
          bool(jnp.array_equal(mask_k, mask_j)))
    expect_erased = older(s_prev)
    size0 = int(t.size())
    del st, mask_k, mask_j
    er = compiled(lambda t, p: (lambda r: (r.table, r.swept))(t.erase_if(p)),
                  t, erase_pred, donate=(0,), name="erase_if")
    t, swept = er(t, erase_pred)
    size1 = int(t.size())
    check("store erase_if", int(swept) == expect_erased
          and size1 == size0 - expect_erased,
          f"swept={int(swept)} expected={expect_erased} size {size0}->{size1}")
    budget = 1 << 16
    evict_pred = SweepPredicate.score_below(s_last)
    ev = compiled(lambda t, p: (lambda r: (r.table, r.evicted, r.count))(
        t.evict_if(p, budget)), t, evict_pred, donate=(0,), name="evict_if")
    t, stream, count = ev(t, evict_pred)
    count = int(count)
    size2 = int(t.size())
    live = np.asarray(stream.mask)
    sc = (np.asarray(stream.score_hi).astype(np.uint64) << np.uint64(32)
          | np.asarray(stream.score_lo))[live]
    gone = rd(t, np.asarray(stream.key_lo)[live].astype(np.int32)[:B]
              if live.sum() >= B else
              np.pad(np.asarray(stream.key_lo)[live].astype(np.int32),
                     (0, B - int(live.sum())), constant_values=-1))[1]
    check("store evict_if", count == min(budget, size1 - B)
          and size2 == size1 - count and (sc < s_last).all()
          and (np.diff(sc.astype(np.int64)) >= 0).all()
          and not np.asarray(gone).any(),
          f"count={count} size {size1}->{size2}")
    log(f"store phase: {wall():.2f}s; {memory_line(dev)}")
    del t, stream

    small_table_parity(check, seed)


def small_table_parity(check: Checks, seed: int) -> None:
    """8x128 slots through the same op kinds on the kernel backend, the jnp
    backend and the plain-Python `OracleTable`: identical statuses."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import HKVTable, U64
    from repro.core.oracle import OracleTable
    from repro.core.u64 import from_uint64

    cap, dim, n = 8 * 128, 8, 512
    rng = np.random.default_rng(seed)
    universe = rng.choice(np.uint64(1) << np.uint64(62), size=3 * cap,
                          replace=False).astype(np.uint64)
    tables = {b: HKVTable.create(capacity=cap, dim=dim, buckets_per_key=2,
                                 backend=b) for b in ("kernel", "jnp")}
    oracle = OracleTable(cap, dim, buckets_per_key=2)
    same, steps = True, 0
    for step in range(8):
        keys = universe[rng.choice(len(universe), size=n, replace=False)]
        vals = rng.integers(0, 1000, size=(n, dim)).astype(np.float32)
        st = {}
        for b, t in tables.items():
            r = t.insert_or_assign(from_uint64(keys), jnp.asarray(vals))
            tables[b] = r.table
            st[b] = np.asarray(r.status)
        st["oracle"] = np.asarray(oracle.insert_or_assign(keys, vals))
        same &= (np.array_equal(st["kernel"], st["jnp"])
                 and np.array_equal(st["kernel"], st["oracle"]))
        probe = universe[rng.choice(len(universe), size=n, replace=False)]
        fk = tables["kernel"].find(from_uint64(probe))
        fj = tables["jnp"].find(from_uint64(probe))
        fo = np.array([oracle.locate(int(k)) is not None for k in probe])
        same &= (np.array_equal(np.asarray(fk.found), np.asarray(fj.found))
                 and np.array_equal(np.asarray(fk.found), fo)
                 and np.array_equal(np.asarray(fk.values),
                                    np.asarray(fj.values)))
        steps += 1
    counts = np.bincount(st["kernel"].astype(np.int64), minlength=5).tolist()
    check("small table kernel==jnp==oracle", same,
          f"{steps} insert+find steps, last status counts {counts}")


# ----------------------------------------------------------------------------
# phase: serving over a tiered table with a host-resident cold tier
# ----------------------------------------------------------------------------

def phase_serve(check: Checks, dev, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import U64, TieredHKVTable
    from repro.core.table import jit_returning_table
    from repro.core.u64 import from_uint64
    from repro.data.synthetic import rank_keys, zipf_keys
    from repro.launch import serve
    from repro.serving import EmbeddingRequest

    wall = Timer()
    dim = 64
    cold_cap, free = serve_cold_capacity(dim)
    table = TieredHKVTable.create(hot_capacity=SERVE_HOT,
                                  cold_capacity=cold_cap, dim=dim)
    jax.block_until_ready(table)
    assert_kernel_backend(check, "serve hot tier", table.hot)
    assert_kernel_backend(check, "serve cold tier", table.cold)
    kind = table.cold.state.values.sharding.memory_kind
    check("serve cold plane in pinned_host", kind == "pinned_host", kind)
    log(f"serve: hot tier {SERVE_HOT} slots, cold tier {cold_cap} slots "
        f"(config D's {SERVE_COLD_PUBLISHED} cut "
        f"x{SERVE_COLD_PUBLISHED // cold_cap}: two versions of its value "
        f"plane fit {free} free host bytes); hot bytes="
        f"{tree_bytes(table.hot.state)} cold bytes="
        f"{tree_bytes(table.cold.state)}; {memory_line(dev)}")

    # warm start: the table a serving replica restores holds the popular
    # keys.  Load ranks past the hot capacity, least popular first, so the
    # hot tier fills and its LRU victims demote to the host tier (a
    # quarter filling it: a cold bucket takes the demotions of two hot
    # buckets, and at half full the fullest would overflow)
    n_load = SERVE_HOT + cold_cap // 4
    B = 1 << 20

    def load(t, kh, kl):
        vals = rows_of(kl.astype(jnp.int32) & 0x7FFFFFFF, dim)
        r = t.insert_or_assign(U64(kh, kl), vals)
        return r.table, (r.dropped, r.demoted)

    ranks = np.arange(n_load - 1, -1, -1, dtype=np.int64)
    keys = rank_keys(ranks)
    ld = jit_returning_table(load, donate_argnums=(0,))
    dropped = demoted = 0
    tm = Timer()
    for i, start in enumerate(range(0, n_load, B)):
        kb = from_uint64(keys[start:start + B])
        table, (dr, dm) = ld(table, kb.hi, kb.lo)
        dropped += int(dr)
        demoted += int(dm)
        if i == 0:
            log(f"compile + first batch, tiered insert_or_assign batch={B}: "
                f"{tm():.2f}s")
        if i % 8 == 0:
            log(f"serve warm load: batch {i}, {tm():.2f}s, demoted="
                f"{demoted}; {memory_line(dev)}")
    hot_size, cold_size = int(table.hot.size()), int(table.cold.size())
    size = int(table.size())
    log(f"serve warm load: {n_load} distinct keys in {tm():.2f}s; hot "
        f"size={hot_size} cold size={cold_size} distinct={size} "
        f"demoted={demoted} dropped={dropped}; {memory_line(dev)}")
    check("serve load conservation", size + dropped == n_load,
          f"{size} + {dropped} vs {n_load}")
    check("serve load demoted to host tier", cold_size > 0)
    admitted = [keys]
    del kb

    argv = ["--hot-capacity", str(SERVE_HOT), "--cold-capacity",
            str(cold_cap), "--dim", str(dim), "--wave-size", str(SERVE_WAVE),
            "--waves", str(SERVE_WAVES), "--admission", "continuous",
            "--zipf-alpha", "1.05", "--update-read-ratio", "0.25",
            "--miss-policy", "admit", "--seed", str(seed)]
    log(f"serve: repro.launch.serve {' '.join(argv)}")
    tm = Timer()
    run = serve.main(argv, table=table)    # consumes `table`
    del table
    log(f"serve run: {tm():.2f}s; {memory_line(dev)}")

    # the engine's waves against a one-shot find on the table each read
    eng, pub = run.engine, run.publisher
    rng = np.random.default_rng(seed + 7)
    find = None
    same = True
    served = []
    for w in range(2):
        _version, snap = pub.snapshot()
        keys = zipf_keys(rng, SERVE_WAVE, 1.05, 2 * snap.cold.capacity)
        k = from_uint64(keys)
        if find is None:
            find = compiled(lambda t, kh, kl: (lambda r: (r.values, r.found))(
                t.find(U64(kh, kl), promote=False)), snap, k.hi, k.lo,
                name="tiered find (one-shot)")
        vals, found = (np.asarray(x) for x in find(snap, k.hi, k.lo))
        served.append(keys)
        req = EmbeddingRequest(rid=10_000 + w, keys=keys)
        eng.submit(req)
        eng.run_until_drained()
        same &= (req.done and np.array_equal(req.found, found)
                 and np.array_equal(req.values, vals))
        del snap
    check("serve waves == one-shot find", same)

    # the trainer's chain starts at the warm-loaded table and admits its
    # step keys: it holds each key admitted exactly once, less what the
    # warm load reported dropped at the cold boundary
    trained = run.trainer.table
    t_hot, t_cold = int(trained.hot.size()), int(trained.cold.size())
    t_size = int(trained.size())
    t_distinct = len(np.unique(np.concatenate(admitted + run.trained)))
    check("serve conservation",
          t_size + dropped == t_distinct and t_cold > 0,
          f"trainer chain: hot size={t_hot} cold size={t_cold} "
          f"distinct={t_size} + dropped={dropped} vs {t_distinct} "
          f"distinct keys admitted")
    # the engine's admissions are offers on top of the trainer's last
    # publish (a publish supersedes them), so the served table holds the
    # trainer's chain and at most every key either side admitted
    final = pub.table
    size = int(final.size())
    everything = len(np.unique(np.concatenate(
        admitted + run.trained + run.served + served)))
    m = run.metrics
    log(f"serve: {m.waves} waves {m.keys} keys hit={m.hit_rate:.4f} "
        f"hot={m.hot_rate:.4f} published={pub.published} "
        f"offered={pub.offered}; served table hot size="
        f"{int(final.hot.size())} cold size={int(final.cold.size())} "
        f"distinct={size}; phase {wall():.2f}s; {memory_line(dev)}")
    check("serve served table holds the trainer chain",
          t_size <= size <= everything,
          f"{t_size} <= {size} <= {everything}")


# ----------------------------------------------------------------------------
# phase: config B owner-sharded over four chips
# ----------------------------------------------------------------------------

def phase_sharded(check: Checks, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.hkv_dlrm import PAPER_CONFIGS
    from repro.distributed.table_sharding import ShardedHKVTable
    from repro.launch.mesh import make_mesh

    wall = Timer()
    devices = jax.devices()[:4]
    mesh = make_mesh((4,), ("data",))
    emb = PAPER_CONFIGS["B"].embedding()
    # uniform keys: each chip sends each owner B/16 keys give or take a
    # few hundred, so a 1.25x routing budget overflows none and the owner
    # probes 1.25x (not the default 2x) padded lanes per key
    t = ShardedHKVTable.create(mesh, emb, capacity_factor=1.25)
    jax.block_until_ready(t.state)
    dim = emb.dim
    per_dev = {}
    for leaf in jax.tree.leaves(t.state):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    log(f"sharded: config B capacity={t.capacity} dim={dim} on "
        f"{len(devices)} chips; bytes per device {per_dev}")
    check("sharded table spread over 4 chips", len(per_dev) == 4
          and min(per_dev.values()) == max(per_dev.values()))
    n_fill = t.capacity // 2
    B = B_BATCH

    def insert(t, start):
        tok = tokens(seed, start, B)
        r = t.insert_or_assign(emb.keys_of(tok), rows_of(tok, dim))
        return r.table, r.overflow.sum(), jnp.sum(r.ok)

    ins = compiled(insert, t, jnp.uint32(0), donate=(0,),
                   name=f"sharded insert_or_assign batch={B}")
    ovf = ok = 0
    tm = Timer()
    for s in range(0, n_fill, B):
        t, o, k = ins(t, jnp.uint32(s))
        ovf += int(o)
        ok += int(k)
    lam = float(t.load_factor())
    log(f"sharded fill: {n_fill} keys in {tm():.2f}s, lambda={lam} "
        f"admitted={ok} overflow={ovf}")
    check("sharded fill", ok == n_fill and ovf == 0 and abs(lam - 0.5) < 0.01,
          f"admitted={ok}/{n_fill} overflow={ovf} lambda={lam}")

    def find(t, tok):
        r = t.find(emb.keys_of(tok))
        return r.values, r.found, r.overflow.sum()

    fd = compiled(find, t, tokens(seed, 0, B), name="sharded find")
    good, ovf = True, 0
    for s in range(0, n_fill, B):
        tok = tokens(seed, s, B)
        vals, found, o = fd(t, tok)
        ovf += int(o)
        good &= bool(jnp.all(found)) and bool(
            jnp.array_equal(vals, rows_of(tok, dim)))
    check("sharded every written key found with its row", good and ovf == 0)
    vals, found, o = fd(t, tokens(seed, 1 << 30, B))
    check("sharded unseen keys miss", not bool(jnp.any(found))
          and int(o) == 0)

    tok = tokens(seed, 0, B)
    grads = jnp.full((B, dim), 0.5, jnp.float32)
    before = fd(t, tok)[0]
    upd = compiled(lambda t, tok, g: t.apply_grads(tok, g), t, tok, grads,
                   donate=(0,), name="sharded apply_grads")
    t = upd(t, tok, grads)
    after, found, o = fd(t, tok)
    moved = bool(jnp.all(found)) and bool(jnp.all(after < before))
    check("sharded apply_grads trains every row", moved and int(o) == 0)
    log(f"sharded phase: {wall():.2f}s; " + "; ".join(
        f"dev{d.id} {memory_line(d)}" for d in devices))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs only on a TPU chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {compile_cache.enable()}")
    check = Checks()
    wall = Timer()
    if args.chips == 4:
        phase_sharded(check, args.seed)
    else:
        phase_store(check, dev, args.seed)
        gc.collect()
        log(f"store released; {memory_line(dev)}")
        phase_serve(check, dev, args.seed)
    ok = not check.failed
    log(f"total {wall():.2f}s; failed checks: {check.failed or 'none'}")
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
