"""Exp#2 (paper §5.3, Figs. 7/8): end-to-end API throughput across the
paper's configs A/B/C (dim 8/32/64) at λ=0.5 and λ=1.0, plus the tiered
(config D analogue) key-side-vs-value-copy decomposition.

Reproduced structure: find* (pointer-returning / key-side only) is
dimension-INDEPENDENT; find (value copy) scales with dim; assign varies
little with λ (non-structural); insert_or_assign pays a bounded eviction
overhead at λ=1.0.

All ops run through the `HKVTable` handle; the inserter backend is part
of the handle (DESIGN.md §4):

    PYTHONPATH=src python -m benchmarks.exp2_throughput --backend kernel

'jnp' (default) times the pure-jnp batch closure; 'kernel' times the
Pallas paths (fused find_scan readers + upsert_scan inserters); 'fused'
runs the dedicated reader arm instead — per-λ fused-find timings, the
per-query work counters whose flat curve is the paper's λ-independence
claim, and a launch-accounting row vs the replaced digest_scan+gather
composition.  Off-TPU the kernels execute in interpret mode — the
numbers then measure the Python interpreter, not the hardware, so kernel
runs shrink the batch to stay tractable and are labelled accordingly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, fill_table, kv_per_s, make_insert_jit, time_fn
from repro.core import HKVTable, U64, u64

CAPACITY = 64 * 128
BATCH = 4096
CONFIGS = {"A": 8, "B": 32, "C": 64}


def _insert_batch(backend: str) -> int:
    """Interpret-mode kernels pay a per-grid-step Python cost off-TPU;
    keep the measured batch small enough to finish in seconds."""
    if backend == "kernel" and jax.default_backend() != "tpu":
        return 512
    return BATCH


def _fill(table, rng, lam, ins):
    n = int(lam * table.capacity)
    keys = rng.integers(0, 2**50, size=n).astype(np.uint64)
    return fill_table(table, keys, ins=ins), keys


def _count_launches(state, cfg, keys):
    """Trace-time kernel-launch accounting: invocations of the fused
    find_scan vs the digest_scan+gather composition it replaced.  The
    wrappers resolve these module attributes at call time, so swapping in
    counting shims (restored in `finally`) observes the real dispatch."""
    from repro.core import ops as core_ops
    from repro.kernels import digest_scan as _ds
    from repro.kernels import find_scan as _fs
    from repro.kernels import gather as _ga
    from repro.kernels import ops as kops

    slots = [(_fs, "find_scan"), (_ds, "digest_scan"),
             (_ga, "gather_rows")]
    originals = {(m, n): getattr(m, n) for m, n in slots}
    counts = {"n": 0}

    def shim(orig):
        def f(*a, **kw):
            counts["n"] += 1
            return orig(*a, **kw)
        return f

    try:
        for m, n in slots:
            setattr(m, n, shim(originals[(m, n)]))
        core_ops.find(state, cfg, keys, backend="kernel")
        fused = counts["n"]
        counts["n"] = 0
        kops.find_composed_kernel(state, cfg, keys)
        composed = counts["n"]
    finally:
        for (m, n), v in originals.items():
            setattr(m, n, v)
    return fused, composed


def _fused_reader_run(csv: Csv, rng):
    """--backend fused: the PR-6 reader-path arm.  Times the single-pass
    fused find across load factors, reports its per-query work counters
    (the find-curve SHAPE: flat in λ), and counts kernel launches against
    the replaced digest_scan+gather composition."""
    from repro.core import find as find_mod
    from repro.core import ops as core_ops

    dim = 32
    batch = _insert_batch("kernel")     # interpret-mode tractable off-TPU
    ins = make_insert_jit()
    find_j = jax.jit(lambda t, h, l: t.find(U64(h, l)).values)
    work = {}
    for lam in (0.5, 0.75, 1.0):
        table = HKVTable.create(capacity=CAPACITY, dim=dim,
                                buckets_per_key=2, backend="jnp")
        table, keys = _fill(table, rng, lam, ins)
        table = table.with_backend("kernel")
        hot = u64.from_uint64(rng.choice(keys, size=batch))
        probe = find_mod.probe_keys(table.cfg, hot)
        # rows touched per query: candidate bucket rows actually scanned
        # (bucket2 can alias bucket1) + exactly ONE fused value row
        meta = 1.0 + float(np.asarray(probe.bucket2 != probe.bucket1).mean())
        work[lam] = meta + 1.0
        t = time_fn(find_j, table, hot.hi, hot.lo)
        r = core_ops.find(table.state, table.cfg, hot, backend="kernel")
        hit = float(np.asarray(r.found).mean())
        csv.row(f"fused-find/dim={dim}/lf={lam}", t,
                f"{kv_per_s(batch, t)/1e6:.2f}M-KV/s,"
                f"rows_per_find={work[lam]:.2f}(meta={meta:.2f}+value=1),"
                f"hit_rate={hit:.2f}")
    lo, hi = min(work.values()), max(work.values())
    csv.row("fused-find/curve-shape", None,
            f"work-variation={100 * (hi - lo) / lo:.1f}% over lf 0.5-1.0 "
            "[paper: find cost is λ-independent]")
    fused_l, composed_l = _count_launches(table.state, table.cfg, hot)
    csv.row("fused-find/launches", None,
            f"fused={fused_l},composed={composed_l},"
            f"eliminated={composed_l - fused_l}/find")
    return csv


def run(csv: Csv | None = None, backend: str = "jnp"):
    tag = "" if backend == "jnp" else (
        " [readers: fused find arm]" if backend == "fused"
        else f" [inserters backend={backend}]")
    csv = csv or Csv(f"Exp#2 API throughput (configs A-C, Figs. 7/8){tag}")
    rng = np.random.default_rng(1)
    if backend == "fused":
        return _fused_reader_run(csv, rng)
    ibatch = _insert_batch(backend)
    ins_shared = make_insert_jit()
    for name, dim in CONFIGS.items():
        for lam in (0.5, 1.0):
            # fill on the jnp backend (interpret-mode kernels would make
            # the fill dominate off-TPU), measure on the requested one
            table = HKVTable.create(capacity=CAPACITY, dim=dim, backend="jnp")
            table, keys = _fill(table, rng, lam, ins_shared)
            table = table.with_backend(backend)
            hot = u64.from_uint64(rng.choice(keys, size=BATCH))
            hot_i = u64.from_uint64(rng.choice(keys, size=ibatch))
            vals = jnp.asarray(rng.normal(size=(ibatch, dim)), jnp.float32)

            find_j = jax.jit(lambda t, h, l: t.find(U64(h, l)).values)
            findp_j = jax.jit(lambda t, h, l: t.find_ptr(U64(h, l)).row)
            cont_j = jax.jit(lambda t, h, l: t.contains(U64(h, l)))
            ins_j = jax.jit(
                lambda t, h, l, v: t.insert_or_assign(U64(h, l), v).table)
            ine_j = jax.jit(
                lambda t, h, l, v: t.insert_and_evict(U64(h, l), v).table)
            asg_j = jax.jit(lambda t, h, l, v: t.assign(U64(h, l), v))

            for api, fn, n, args in (
                ("find", find_j, BATCH, (table, hot.hi, hot.lo)),
                ("find_ptr", findp_j, BATCH, (table, hot.hi, hot.lo)),
                ("contains", cont_j, BATCH, (table, hot.hi, hot.lo)),
                ("insert_or_assign", ins_j, ibatch, (table, hot_i.hi, hot_i.lo, vals)),
                ("insert_and_evict", ine_j, ibatch, (table, hot_i.hi, hot_i.lo, vals)),
                ("assign", asg_j, ibatch, (table, hot_i.hi, hot_i.lo, vals)),
            ):
                t = time_fn(fn, *args)
                csv.row(f"{api}/cfg{name}(dim={dim})/lf={lam}", t,
                        f"{kv_per_s(n, t)/1e6:.2f}M-KV/s")

    # config D (paper Table 5): HBM keys + HMEM (host-tier) values. The
    # paper's claim: the pointer-returning find* is tier-INDEPENDENT (keys
    # never leave HBM); value-copying find pays the host link per row.

    tabled = HKVTable.create(capacity=CAPACITY, dim=64, value_tier="hmem",
                             backend="jnp")
    tabled, keys = _fill(tabled, rng, 1.0, ins_shared)
    tabled = tabled.with_backend(backend)
    hot = u64.from_uint64(rng.choice(keys, size=BATCH))
    findd_j = jax.jit(lambda t, h, l: t.find(U64(h, l)).values)
    findpd_j = jax.jit(lambda t, h, l: t.find_ptr(U64(h, l)).row)
    td = time_fn(findd_j, tabled, hot.hi, hot.lo)
    tpd = time_fn(findpd_j, tabled, hot.hi, hot.lo)
    csv.row("find/cfgD(dim=64,hmem)/lf=1.0", td,
            f"{kv_per_s(BATCH, td)/1e6:.2f}M-KV/s,values-cross-tier")
    csv.row("find_ptr/cfgD(dim=64,hmem)/lf=1.0", tpd,
            f"{kv_per_s(BATCH, tpd)/1e6:.2f}M-KV/s,key-side-only"
            f"[paper:96% of pure-HBM]")
    return csv


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="jnp",
                    choices=("auto", "jnp", "kernel", "fused"),
                    help="table-op backend (kernel = Pallas paths; fused = "
                         "the reader-path launch-accounting arm)")
    run(backend=ap.parse_args().backend)
