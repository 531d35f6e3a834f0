"""A reader added as files only (`tests/test_hkvbench_entry_contract.py`):
closed-loop `HKVTable.find` of keys drawn uniformly from the slots of a
loaded table.  Every lane of every batch is checked, found and with its
exact row; `counters` reports the lanes found."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from hkvbench import build, common, keys as keymod, reference
from hkvbench.harness import Reading, Window

TINY = ({"capacity": 8 * 128}, {"batch": 128})
FAULTS = ("control", "half_batch", "altered_answer")


@dataclasses.dataclass
class State:
    table: object
    gen: object
    find: object
    kdata: object
    outs: list = dataclasses.field(default_factory=list)


def setup(run):
    from repro.core import HKVTable, U64

    cfg = common.embedding(run.cell.config).config()
    table = HKVTable.wrap(build.build(cfg, run.seed), cfg)
    n, s = int(run.cell.traffic["batch"]), cfg.slots_per_bucket

    @jax.jit
    def gen(key_hi, key_lo, kdata, i):
        k = jax.random.fold_in(jax.random.wrap_key_data(kdata), i)
        slot = jax.random.randint(k, (n,), 0, cfg.capacity, jnp.int32)
        return key_hi[slot // s, slot % s], key_lo[slot // s, slot % s]

    @jax.jit
    def find(t, kh, kl):
        r = t.find(U64(kh, kl))
        return r.values, r.found

    st = State(table, gen, common.planted(run.fault, find, kind="reader"),
               jax.random.key_data(keymod.prng_key(run.seed, 3)))
    jax.block_until_ready(st.find(table, *_keys(st, 0)))
    return st


def _args(st, i):
    s = st.table.state
    return s.key_hi, s.key_lo, st.kdata, jnp.uint32(i)


def _keys(st, i):
    return st.gen(*_args(st, i))


def window(run, st) -> Window:
    def dispatch(i):
        kh, kl = _keys(st, i)
        return (kl,) + tuple(st.find(st.table, kh, kl))

    t_open, t_close, batches = common.closed_loop(
        run.seconds, int(run.cell.traffic["in_flight"]), dispatch,
        lambda i, out: st.outs.append(out))
    return Window(t_open=t_open, t_close=t_close, attempted=batches,
                  failed=0, keys=batches * int(run.cell.traffic["batch"]))


def programs(run, st) -> list:
    return [common.index_program(), (st.gen, _args(st, 0)),
            (st.find, (st.table,) + tuple(_keys(st, 0)))]


def counters(run, st, win) -> dict:
    return {"lanes_found": sum(int(np.sum(found)) for _, _, found in st.outs)}


def release(run, st, win):
    st.outs = [tuple(np.asarray(x) for x in out) for out in st.outs]


def resident(run, st) -> int:
    return int(st.table.size())


def check(run, st, win):
    dim = int(run.cell.config["dim"])
    st.table = None
    wrong = 0
    for kl, vals, found in st.outs:
        if run.fault == "control":
            vals = common.control_rows(kl, dim)
        want = reference.rows_np(kl, dim)
        wrong += int(np.sum(~found.astype(bool) | np.any(vals != want, 1)))
    return [Reading("probe_lanes_wrong", wrong, 0)]
