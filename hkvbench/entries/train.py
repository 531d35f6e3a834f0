"""Closed-loop training steps: `HKVEmbedding.lookup_train` (find_or_insert
of the step's ids, admitting new ones at lambda 1.0 by eviction) and then
`apply_grads` (per-id gradient sums, rowwise adagrad in place) with the
gradient of 0.5 |row|^2, so each step's update depends on the rows its
lookup returned.

Traffic keys: `lookups_per_step` ids per step drawn zipf(`zipf_alpha`)
over `id_space` popularity ranks; `checked_steps` steps run in set-up through the window's own step
program, and the window continues from them on the same table.

Checked after the window, against the plain model of the table following
every step on the device (`reference.train_step_device`): every row each
checked step's lookup returned, the rows returned to `window_lanes` lanes
drawn from the seed in every window step, the key held by every slot of
the table the window leaves, and, for ids drawn from the seed among all
the steps' ids, whether the table holds them and their whole row
(embedding and adagrad column).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from hkvbench import build, common, keys as keymod, reference
from hkvbench.harness import Reading, Window

SAMPLE = 16384      # ids whose row is read back after the window
# widest gap of a row value, |program - reference| / (|reference| + 1);
# see PERF.md for the readings it was set from
ROW_GAP_LIMIT = 1e-4


def _gap(got, want) -> float:
    if not np.size(want):
        return 0.0
    return float(np.max(np.abs(got - want) / (np.abs(want) + 1.0)))


@dataclasses.dataclass
class State:
    table: object
    cfg: object
    gen: object
    step: object
    pick: object
    lanes: object
    kdata: object
    c: object
    checked: list          # rows returned by each checked step, every lane
    picked: list = dataclasses.field(default_factory=list)  # window steps
    steps: int = 0         # window steps
    sample: tuple = ()     # (tokens, found, rows) read back after the window


def tokgen(tr: dict):
    """`gen(kdata, i, c)`: step i's tokens."""
    n = int(tr["lookups_per_step"])
    alpha, m = float(tr["zipf_alpha"]), int(tr["id_space"])

    @jax.jit
    def gen(kdata, i, c):
        k = jax.random.fold_in(jax.random.wrap_key_data(kdata), i)
        return keymod.token(keymod.zipf_ranks(k, n, alpha, m), c)

    return gen


def _tokens(st, i):
    return st.gen(st.kdata, jnp.uint32(i), st.c)


def setup(run):
    tr = run.cell.traffic
    emb = common.embedding(run.cell.config)
    cfg = emb.config()
    from repro.core import HKVTable

    table = HKVTable.wrap(build.build(cfg, run.seed), cfg, emb.backend)
    run.mark("build")

    def step(t, tok):
        t, rows = emb.lookup_train(t, tok)
        return emb.apply_grads(t, tok, rows), rows

    step = jax.jit(common.planted(run.fault, step, kind="train"),
                   donate_argnums=(0,))
    n = int(tr["lookups_per_step"])
    rng = np.random.default_rng(run.seed % (1 << 63))
    lanes = jnp.asarray(np.sort(rng.choice(n, int(tr["window_lanes"]),
                                           replace=False)).astype(np.int32))
    pick = jax.jit(lambda rows, lanes: rows[lanes])
    kdata = jax.random.key_data(keymod.prng_key(run.seed, 2))
    c = jnp.uint32(keymod.offset(run.seed))
    st = State(table, cfg, tokgen(tr), step, pick, lanes, kdata, c, [])
    for i in range(int(tr["checked_steps"])):
        tok = st.gen(kdata, jnp.uint32(i), c)
        st.table, rows = step(st.table, tok)
        jax.block_until_ready(pick(rows, lanes))
        st.checked.append(np.asarray(rows))
    return st


def window(run, st) -> Window:
    tr = run.cell.traffic
    first = int(tr["checked_steps"])

    def dispatch(i):
        tok = st.gen(st.kdata, jnp.uint32(first + i), st.c)
        st.table, rows = st.step(st.table, tok)
        st.picked.append(st.pick(rows, st.lanes))
        return rows         # the table is donated to the next step

    t_open, t_close, st.steps = common.closed_loop(
        run.seconds, int(tr["in_flight"]), dispatch, lambda i, out: None)
    return Window(t_open=t_open, t_close=t_close, attempted=st.steps,
                  failed=0, keys=st.steps * int(tr["lookups_per_step"]))


def release(run, st, win):
    """The window's picked rows to the host, and a sample of every step's
    ids read back from the table the window leaves."""
    from repro.core import U64

    st.picked = [np.asarray(p) for p in st.picked]
    total = len(st.checked) + st.steps
    seen = np.unique(np.concatenate([np.asarray(_tokens(st, i))
                                     for i in range(total)]))
    rng = np.random.default_rng((run.seed + 1) % (1 << 63))
    pick = np.sort(rng.choice(seen, min(SAMPLE, len(seen)), replace=False))
    k = jnp.asarray(pick.astype(np.uint32))
    r = jax.jit(lambda t, kk: t.find_rows(kk))(
        st.table, U64(jnp.zeros_like(k), k))
    st.sample = (pick, np.asarray(r.found), np.asarray(r.rows))


def resident(run, st) -> int:
    return int(st.table.size())


def check(run, st, win):
    cfg = run.cell.config
    dual = int(cfg["buckets_per_key"]) == 2
    opt = cfg["optimizer"]
    if opt["name"] != "rowwise_adagrad":
        raise ValueError("the reference trains with rowwise adagrad")
    s = st.table.state
    key_ok = jnp.all((s.key_hi == 0) | (s.key_lo == jnp.uint32(0xFFFFFFFF)))
    key_lo = s.key_lo
    st.table = None
    del s
    keys, scores, rows, clock = reference.train_state(
        run.seed, cfg, build.ranks_drawn(st.cfg))
    gap = 0.0
    for i in range(len(st.checked) + st.steps):
        keys, scores, rows, want = reference.train_step_device(
            keys, scores, rows, _tokens(st, i), jnp.int32(clock + 1 + i),
            dual, float(opt["lr"]), 1e-10)
        if i < len(st.checked):
            got, want = st.checked[i], np.asarray(want)
        else:
            got = st.picked[i - len(st.checked)]
            want = np.asarray(want[st.lanes])
        if run.fault == "control":
            got = common.to_bf16(want)
        gap = max(gap, _gap(got, want))
    mine = jnp.where(key_lo == jnp.uint32(0xFFFFFFFF), -1,
                     key_lo.astype(jnp.int32))
    table_wrong = int(jnp.sum(mine != keys)) + int(not bool(key_ok))
    tok, found, got = st.sample
    held, hb, hs = reference.locate_device(
        keys, jnp.asarray(tok.astype(np.int32)), dual)
    want = np.asarray(rows[hb, hs])[np.asarray(held)]
    held = np.asarray(held)
    got = got[held]
    if run.fault == "control":
        got = common.to_bf16(want)
    gap = max(gap, _gap(got, want))
    return [Reading("train_window_unchecked", int(not st.picked), 0),
            Reading("train_table_wrong", table_wrong, 0),
            Reading("train_found_wrong", int(np.sum(held != found)), 0),
            Reading("train_rows_gap", gap, ROW_GAP_LIMIT)]
