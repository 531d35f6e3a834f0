"""Closed-loop `HKVTable.insert_or_assign` batches of keys never inserted,
into a full table: every key resolves by eviction or rejection (the
paper's full-capacity contract).

Traffic keys: `batch` (keys per call); `keys: new` takes batch i's keys
from ranks N + i * batch onward, past the N ranks the build drew.

Checked after the window, against the plain model replaying the same
batches from the same loaded table (`reference.upsert_device`): the status
of every lane of every batch, the key held by every slot of the table the
window leaves, and, for keys drawn from the seed among the window's,
whether the table holds them and their exact rows.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from hkvbench import build, common, keys as keymod, reference
from hkvbench.harness import Reading, Window

SAMPLE = 4096


@dataclasses.dataclass
class State:
    table: object
    cfg: object
    gen: object
    insert: object
    c: object
    statuses: list = dataclasses.field(default_factory=list)
    batches: int = 0
    key_lo: object = None
    key_hi_ok: bool = True
    sample: tuple = ()


def _first_rank(cfg, i: int, n: int) -> int:
    return build.ranks_drawn(cfg) + i * n


def setup(run):
    from repro.core import HKVTable, U64

    emb = common.embedding(run.cell.config)
    cfg = emb.config()
    n = int(run.cell.traffic["batch"])
    table = HKVTable.wrap(build.build(cfg, run.seed), cfg, emb.backend)
    run.mark("build")

    @jax.jit
    def gen(c, start):
        return keymod.token(start + jnp.arange(n, dtype=jnp.uint32), c)

    def insert(t, tok):
        r = t.insert_or_assign(U64(jnp.zeros_like(tok, jnp.uint32),
                                   tok.astype(jnp.uint32)),
                               keymod.rows_of(tok, cfg.dim))
        return r.table, r.status

    insert = jax.jit(common.planted(run.fault, insert, kind="insert"),
                     donate_argnums=(0,))
    c = jnp.uint32(keymod.offset(run.seed))
    # warm up without writing: compile ahead of time for the window
    tok = jax.block_until_ready(gen(c, jnp.uint32(0)))
    insert = insert.lower(table, tok).compile()
    return State(table, cfg, gen, insert, c)


def window(run, st) -> Window:
    tr = run.cell.traffic
    n = int(tr["batch"])

    def dispatch(i):
        tok = st.gen(st.c, jnp.uint32(_first_rank(st.cfg, i, n)))
        st.table, status = st.insert(st.table, tok)
        return st.table, status

    def done(i, out):
        st.statuses.append(out[1])

    t_open, t_close, st.batches = common.closed_loop(
        run.seconds, int(tr["in_flight"]), dispatch, done)
    return Window(t_open=t_open, t_close=t_close, attempted=st.batches,
                  failed=0, keys=st.batches * n)


def release(run, st, win):
    from repro.core import U64

    n = int(run.cell.traffic["batch"])
    st.statuses = [np.asarray(s) for s in st.statuses]
    rng = np.random.default_rng(run.seed % (1 << 63))
    ranks = np.sort(rng.choice(st.batches * n, min(SAMPLE, st.batches * n),
                               replace=False)) + _first_rank(st.cfg, 0, n)
    tok = reference.token_np(ranks, reference.offset(run.seed))
    k = jnp.asarray(tok.astype(np.uint32))
    r = jax.jit(lambda t, kk: t.find_rows(kk))(
        st.table, U64(jnp.zeros_like(k), k))
    st.sample = (tok, np.asarray(r.found), np.asarray(r.rows))


def resident(run, st) -> int:
    return int(st.table.size())


def check(run, st, win):
    cfg = run.cell.config
    dim = int(cfg["dim"])
    dual = int(cfg["buckets_per_key"]) == 2
    n = int(run.cell.traffic["batch"])
    s = st.table.state
    key_ok = jnp.all((s.key_hi == 0) | (s.key_lo == jnp.uint32(0xFFFFFFFF)))
    key_lo = s.key_lo
    st.table = None
    del s
    keys, scores, clock = reference.device_state(run.seed, cfg,
                                                 build.ranks_drawn(st.cfg))
    status_wrong = 0
    for i, got in enumerate(st.statuses):
        tok = reference.token_np(np.arange(n) + _first_rank(st.cfg, i, n),
                                 reference.offset(run.seed))
        keys, scores, want = reference.upsert_device(
            keys, scores, jnp.asarray(tok.astype(np.int32)),
            jnp.int32(clock + 1 + i), dual, min(n, 1 << 14))
        status_wrong += int(np.sum(got.astype(np.int64)
                                   != np.asarray(want)))
    mine = jnp.where(key_lo == jnp.uint32(0xFFFFFFFF), -1,
                     key_lo.astype(jnp.int32))
    table_wrong = int(jnp.sum(mine != keys)) + int(not bool(key_ok))
    tok, found, rows = st.sample
    pad = (-len(tok)) % (1 << 12)
    held = np.asarray(reference.held_device(
        keys, jnp.asarray(np.pad(tok, (0, pad), constant_values=-1)
                          .astype(np.int32)), dual))[:len(tok)]
    want = reference.rows_np(tok[held], dim)
    got = rows[held][:, :dim]
    if run.fault == "control":
        got = common.to_bf16(want)
    rows_wrong = int(np.sum(np.any(got != want, axis=1)))
    rows_wrong += int(np.sum(rows[held][:, dim:] != 0))
    return [Reading("ingest_batches_unchecked", int(not st.statuses), 0),
            Reading("ingest_status_wrong", status_wrong, 0),
            Reading("ingest_table_wrong", table_wrong, 0),
            Reading("ingest_found_wrong", int(np.sum(held != found)), 0),
            Reading("ingest_rows_wrong", rows_wrong, 0)]
