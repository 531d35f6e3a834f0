"""Closed-loop `HKVTable.find` batches over a full table.

Traffic keys: `batch` (keys per call) and `keys`: `resident_uniform`
draws each key uniformly from the slots of the loaded table, so every
lookup hits.  One client keeps `in_flight` batches in flight: it
generates and dispatches the next batch while the device works on the
last (`common.closed_loop`).

Checked after the window: every lane of up to `RETAIN` batches drawn from
the seed (found, and the exact row), and, for a sample of those lanes
drawn from the seed, that the reference's load rule holds the key.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from hkvbench import build, common, keys as keymod, reference
from hkvbench.harness import Reading, Window

RETAIN = 4          # batches whose answers are compared in full
SAMPLE = 4096       # lanes whose residency the reference recomputes

TINY = ({"capacity": 8 * 128}, {"batch": 256})
FAULTS = ("control", "half_batch", "altered_answer")


@dataclasses.dataclass
class State:
    table: object
    cfg: object
    keygen: object
    find: object
    kdata: object
    retained: list
    host: list = dataclasses.field(default_factory=list)


def _keygen(cfg, n: int):
    cap, s = cfg.capacity, cfg.slots_per_bucket

    @jax.jit
    def gen(key_hi, key_lo, kdata, i):
        k = jax.random.fold_in(jax.random.wrap_key_data(kdata), i)
        slot = jax.random.randint(k, (n,), 0, cap, jnp.int32)
        return key_hi[slot // s, slot % s], key_lo[slot // s, slot % s]

    return gen


def setup(run):
    from repro.core import HKVTable, U64

    cfg = common.embedding(run.cell.config).config()
    table = HKVTable.wrap(build.build(cfg, run.seed), cfg)
    run.mark("build")
    gen = _keygen(cfg, int(run.cell.traffic["batch"]))

    @jax.jit
    def find(t, kh, kl):
        r = t.find(U64(kh, kl))
        return r.values, r.found

    find = common.planted(run.fault, find, kind="reader")
    kdata = jax.random.key_data(keymod.prng_key(run.seed, 1))
    st = State(table, cfg, gen, find, kdata, [])
    kh, kl = gen(table.state.key_hi, table.state.key_lo, kdata,
                 jnp.uint32(1 << 30))
    jax.block_until_ready(find(table, kh, kl))
    return st


def window(run, st) -> Window:
    s = st.table.state
    phase = run.seed % 3
    n = int(run.cell.traffic["batch"])

    def dispatch(i):
        kh, kl = st.keygen(s.key_hi, s.key_lo, st.kdata, jnp.uint32(i))
        return (kh, kl) + tuple(st.find(st.table, kh, kl))

    def done(i, out):
        if i % 3 == phase and len(st.retained) < RETAIN:
            st.retained.append(out)

    t_open, t_close, batches = common.closed_loop(
        run.seconds, int(run.cell.traffic["in_flight"]), dispatch, done)
    return Window(t_open=t_open, t_close=t_close, attempted=batches,
                  failed=0, keys=batches * n)


def programs(run, st) -> list:
    s = st.table.state
    args = (s.key_hi, s.key_lo, st.kdata, jnp.uint32(0))
    return [common.index_program(), (st.keygen, args),
            (st.find, (st.table,) + tuple(st.keygen(*args)))]


def release(run, st, win):
    st.host = [tuple(np.asarray(x) for x in r) for r in st.retained]
    st.retained.clear()


def resident(run, st) -> int:
    return int(st.table.size())


def check(run, st, win):
    cfg = st.cfg
    st.table = None
    if not st.host:
        return [Reading("find_batches_unchecked", 1, 0)]
    c = reference.offset(run.seed)
    rows_wrong = found_wrong = 0
    sample_tok, sample_found = [], []
    rng = np.random.default_rng(run.seed % (1 << 63))
    for kh, kl, vals, found in st.host:
        if run.fault == "control":
            vals, found = common.control_rows(kl, cfg.dim), np.ones_like(found)
        want = reference.rows_np(kl, cfg.dim)
        bad_hi = kh != 0
        found_wrong += int(np.sum(~found.astype(bool) | bad_hi))
        rows_wrong += int(np.sum(np.any(vals != want, axis=1)))
        pick = rng.choice(len(kl), min(len(kl), SAMPLE // len(st.host)),
                          replace=False)
        sample_tok.append(kl[pick])
        sample_found.append(found[pick].astype(bool))
    tok = np.concatenate(sample_tok)
    slot = reference.resident_slot(
        reference.rank_np(tok, c), c, cfg.num_buckets, cfg.slots_per_bucket,
        cfg.buckets_per_key == 2, build.ranks_drawn(cfg))
    found_wrong += int(np.sum((slot >= 0) != np.concatenate(sample_found)))
    return [Reading("find_batches_unchecked", 0, 0),
            Reading("find_found_wrong", found_wrong, 0),
            Reading("find_rows_wrong", rows_wrong, 0)]
