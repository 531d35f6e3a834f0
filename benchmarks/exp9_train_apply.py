"""Exp#9: end-to-end DLRM training steps/sec, fused vs composed updater.

The paper's own workload (`examples/dlrm_continuous.py`, config B scaled):
26 sparse fields through one HKV table, dense bottom MLP, dot interaction,
click-through logistic loss.  The measured quantity is the FULL train
step — lookup_train (inserter) + forward/backward + the embedding
gradient apply — under two apply arms, per optimizer variant:

  fused      `HKVEmbedding.apply_grads` as shipped: compacted dedupe +
             segment-sum + ONE structured `update_rows` dispatch (on
             backend='kernel' a single fused update_scan launch)
  composed   the pre-fusion sequence the fused op replaced, as the
             SEPARATE dispatches it actually was: find_rows (locate +
             gather, rows materialize to HBM) -> optimizer apply ->
             assign (locate + scatter) — the gradient apply crosses
             three launch boundaries and round-trips the row batch

The MLP front half (lookup_train + forward/backward + dense update) is
one shared jitted function; the arms differ ONLY in how many dispatches
the gradient apply takes.  That boundary structure is the thing the
fused kernel removes — timing both arms inside one jit would let XLA
CSE/fuse the composed passes back together and measure nothing.  Timings
are CPU-XLA relative numbers (per benchmarks.common); the KERNEL-path
deltas ride along as trace-time launch accounting (shim counters around
the kernel wrappers, like exp2's) plus the `roofline.update_bytes`
model — so the artifact carries steps/sec, launches eliminated, and
bytes saved per update in one place.

    PYTHONPATH=src python -m benchmarks.exp9_train_apply
    PYTHONPATH=src python -m benchmarks.run exp9_train_apply \
        --json-out runs/bench --timestamp ...
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Csv, time_fn
from benchmarks.roofline import update_bytes
from repro.configs.hkv_dlrm import PAPER_CONFIGS, scaled
from repro.core import ops as core_ops
from repro.data import zipf_keys
from repro.embedding.sparse_opt import SparseOptimizer
from repro.models.common import dense_init

BATCH = 128
SCALE = 2**14            # config B capacity 128M -> 8k slots on CPU
OPTIMIZERS = ("sgd", "sgdm", "rowwise_adagrad", "adagrad")


def _make_steps(cfg, emb):
    """(step_fused, step_composed): Python step functions over shared
    jitted pieces.  The front half (lookup + fwd/bwd + dense update) is
    ONE jitted fn both arms call; the apply phase is one dispatch
    (fused) vs three (composed) — the launch structure under test."""
    from repro.core import merge as merge_mod
    from repro.core import u64
    from repro.core.u64 import U64

    d, nf = cfg.dim, cfg.num_sparse
    opt = emb.optimizer

    def forward(params, emb_rows, dense_x):
        z = jax.nn.relu(dense_x @ params["bottom1"]) @ params["bottom2"]
        feats = jnp.concatenate([z[:, None, :], emb_rows], axis=1)
        inter = jnp.einsum("bnd,bmd->bnm", feats, feats)
        iu = jnp.triu_indices(nf + 1, k=1)
        flat = inter[:, iu[0], iu[1]]
        h = jnp.concatenate([z, flat], axis=1)
        return (jax.nn.relu(h @ params["top1"]) @ params["top2"])[:, 0]

    def loss_fn(params, emb_rows, dense_x, labels):
        logits = forward(params, emb_rows, dense_x)
        return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1))

    @jax.jit
    def front(table, params, toks, dense_x, labels):
        table, rows = emb.lookup_train(table, toks)
        loss, (gp, ge) = grad_fn(params, rows, dense_x, labels)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, gp)
        return table, params, ge, loss

    # fused apply: ONE dispatch (dedupe + structured update_rows)
    @jax.jit
    def apply_fused(table, toks, ge):
        return emb.apply_grads(table, toks, ge)

    # composed apply: the dedupe (shared by both routes pre- and
    # post-PR) plus THREE table-op dispatches
    @jax.jit
    def dedupe(toks, ge):
        keys = emb.keys_of(toks)
        g = ge.reshape(-1, d)
        n = g.shape[0]
        dd = merge_mod.dedupe_keys(keys)
        uh = jnp.full((n,), u64.EMPTY_HI, jnp.uint32).at[dd.gid].set(
            keys.hi[dd.idx_sorted])
        ul = jnp.full((n,), u64.EMPTY_LO, jnp.uint32).at[dd.gid].set(
            keys.lo[dd.idx_sorted])
        g_sum = jax.ops.segment_sum(g[dd.idx_sorted], dd.gid,
                                    num_segments=n,
                                    indices_are_sorted=True)
        return uh, ul, g_sum

    @jax.jit
    def gather(table, uh, ul):                 # locate + gather
        r = table.find_rows(U64(uh, ul))
        return r.rows, r.found

    @jax.jit
    def apply_opt(rows, g_sum, found):         # the optimizer pass
        new = opt.apply(rows, g_sum, d)
        return jnp.where(found[:, None], new, rows)

    @jax.jit
    def scatter(table, uh, ul, new):           # locate + scatter
        return table.assign(U64(uh, ul), new)

    def step_fused(table, params, toks, dense_x, labels):
        table, params, ge, loss = front(table, params, toks, dense_x,
                                        labels)
        return apply_fused(table, toks, ge), params, loss

    def step_composed(table, params, toks, dense_x, labels):
        table, params, ge, loss = front(table, params, toks, dense_x,
                                        labels)
        uh, ul, g_sum = dedupe(toks, ge)
        rows, found = gather(table, uh, ul)
        new = apply_opt(rows, g_sum, found)
        return scatter(table, uh, ul, new), params, loss

    return step_fused, step_composed


def _batch(rng, cfg):
    field_keys = np.stack(
        [zipf_keys(rng, BATCH, 0.99, 10**6) ^ np.uint64(f << 56)
         for f in range(cfg.num_sparse)], axis=1)
    toks = jnp.asarray((field_keys & np.uint64(0x7FFFFFFF)).astype(np.int64),
                       jnp.int32)
    dense_x = jnp.asarray(rng.normal(size=(BATCH, cfg.dense_features)),
                          jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, size=BATCH), jnp.float32)
    return toks, dense_x, labels


def _params(cfg, key):
    ks = jax.random.split(key, 4)
    d, nf = cfg.dim, cfg.num_sparse
    return {
        "bottom1": dense_init(ks[0], cfg.dense_features, 64),
        "bottom2": dense_init(ks[1], 64, d),
        "top1": dense_init(ks[2], d + nf * (nf + 1) // 2, 64),
        "top2": dense_init(ks[3], 64, 1),
    }


def _count_kernel_launches(emb, table, toks, grads):
    """Trace-time launch accounting on backend='kernel': the fused
    apply_grads vs the composed kernel sequence it replaced (restored in
    `finally`, exactly like exp2's find counter)."""
    from repro.kernels import digest_scan as _ds
    from repro.kernels import gather as _ga
    from repro.kernels import ops as kops
    from repro.kernels import scatter as _sc
    from repro.kernels import update_scan as _upd

    slots = [(_upd, "update_scan"), (_ds, "digest_scan"),
             (_ga, "gather_rows"), (_sc, "scatter_rows")]
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
        kemb = dataclasses.replace(emb, backend="kernel")
        ktable = table.with_backend("kernel")
        kemb.apply_grads(ktable, toks, grads)
        fused = counts["n"]
        counts["n"] = 0
        keys = kemb.keys_of(toks)
        g_sum = grads.reshape(-1, emb.dim)
        kops.update_composed_kernel(ktable.state, ktable.cfg, keys, g_sum,
                                    emb.optimizer)
        composed = counts["n"]
    finally:
        for (m, n), v in originals.items():
            setattr(m, n, v)
    return fused, composed


def run(csv: Csv | None = None):
    csv = csv or Csv("Exp#9 DLRM train steps/sec: fused vs composed "
                     "updater x optimizer (config B scaled)")
    base = scaled(PAPER_CONFIGS["B"], scale=SCALE)
    key = jax.random.PRNGKey(0)

    for opt_name in OPTIMIZERS:
        opt = SparseOptimizer(opt_name, lr=0.01)
        emb = dataclasses.replace(base.embedding(), optimizer=opt,
                                  backend="jnp")
        rates = {}
        steps = dict(zip(("fused", "composed"), _make_steps(base, emb)))
        for arm in ("fused", "composed"):
            rng = np.random.default_rng(9)         # identical streams/arms
            table = emb.create()
            params = _params(base, key)
            step = steps[arm]
            # warm the table AND the jit cache before timing
            for _ in range(3):
                toks, dense_x, labels = _batch(rng, base)
                table, params, _ = step(table, params, toks, dense_x,
                                        labels)
            toks, dense_x, labels = _batch(rng, base)
            t = time_fn(step, table, params, toks, dense_x, labels,
                        reps=9, warmup=2)
            rates[arm] = 1.0 / t
            uniq = len(np.unique(np.asarray(toks)))
            csv.row(f"step/{opt_name}/{arm}", t,
                    f"{rates[arm]:.1f}steps/s,"
                    f"{BATCH * base.num_sparse}lookups+{uniq}uniq-updates")
        csv.row(f"step/{opt_name}/speedup", None,
                f"fused/composed={rates['fused'] / rates['composed']:.3f}x"
                "[>=1: the fused apply never loses]")

    # kernel-path deltas: launches eliminated (trace-time accounting, tiny
    # table — interpret mode) + the roofline bytes model per update
    opt = SparseOptimizer("rowwise_adagrad", lr=0.01)
    tiny = dataclasses.replace(scaled(PAPER_CONFIGS["B"], scale=2**19),
                               num_sparse=4)
    emb = dataclasses.replace(tiny.embedding(), optimizer=opt,
                              backend="jnp")
    rng = np.random.default_rng(11)
    table = emb.create()
    toks = jnp.asarray(rng.integers(0, 64, size=(32, tiny.num_sparse)),
                       jnp.int32)
    table, _ = emb.lookup_train(table, toks)
    grads = jnp.asarray(rng.normal(size=(32, tiny.num_sparse, tiny.dim)),
                        jnp.float32)
    fused_l, composed_l = _count_kernel_launches(emb, table, toks, grads)
    csv.row("kernel-launches/apply_grads", None,
            f"fused={fused_l},composed={composed_l},"
            f"eliminated={composed_l - fused_l}/step")
    b = update_bytes(base.dim, opt.aux_dim(base.dim), buckets_per_key=2)
    csv.row("bytes-model/cfgB(rowwise_adagrad)", None,
            f"fused={b['fused']}B,composed={b['composed']}B,"
            f"saved={b['composed'] - b['fused']}B/update"
            f"({100 * (b['composed'] - b['fused']) / b['composed']:.0f}%)")
    return csv


if __name__ == "__main__":
    run()
