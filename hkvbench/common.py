"""Pieces the entry modules share: the program's handles for a
configuration file, and the planted faults and control that check the
check."""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from hkvbench import reference
from hkvbench.trace import annotate


def embedding(config: dict):
    """The program's `HKVEmbedding` for a configuration file."""
    from repro.embedding import HKVEmbedding
    from repro.embedding.sparse_opt import SparseOptimizer

    opt = config["optimizer"]
    return HKVEmbedding(
        capacity=int(config["capacity"]), dim=int(config["dim"]),
        optimizer=SparseOptimizer(opt["name"], lr=float(opt["lr"])),
        buckets_per_key=int(config["buckets_per_key"]),
        score_policy=config["score_policy"],
        value_tier=config["value_tier"])


def closed_loop(seconds: float, depth: int, dispatch, done):
    """A closed-loop client keeping `depth` batches in flight.

    `dispatch(i)` enqueues batch i and returns its device outputs; with
    `depth` outstanding the client blocks on the oldest and hands it to
    `done(i, outputs)`.  Once `seconds` have passed it dispatches no more
    and waits for what is in flight.  Returns (t_open, t_close, batches):
    every batch dispatched has completed by t_close."""
    pending = collections.deque()
    t_open = time.perf_counter()
    i = 0

    def retire():
        j, out = pending.popleft()
        with annotate("hkv.block"):
            jax.block_until_ready(out)
        done(j, out)

    while True:
        with annotate("hkv.dispatch"):
            pending.append((i, dispatch(i)))
        i += 1
        if len(pending) >= depth:
            retire()
        if time.perf_counter() - t_open >= seconds:
            break
    while pending:
        retire()
    return t_open, time.perf_counter(), i


def to_bf16(rows) -> np.ndarray:
    """Rows rounded to bfloat16, the precision below the configuration's
    float32, and back: the control's answers."""
    return np.asarray(jnp.asarray(rows, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))


def control_rows(tok, dim: int) -> np.ndarray:
    """The control of a reader: the reference's rows held in bfloat16."""
    return to_bf16(reference.rows_np(tok, dim))


def planted(fault, fn, *, kind: str):
    """`fn` with a fault planted in what it returns (None: `fn` itself).

    reader `fn(table, kh, kl) -> (values, found)`, and
    train `fn(table, tokens) -> (successor, rows returned)`, and
    insert `fn(table, tokens) -> (successor, status)`:
      half_batch       only the even lanes of the batch are served;
      altered_answer   lane 0's row comes back with +1 in its first column
                       (insert: lane 0's status is flipped);
      unchanged_state  (train, insert) the step hands back the table it
                       was given.
    """
    if fault in (None, "control"):
        return fn
    if kind == "reader":
        def wrapped(t, kh, kl):
            vals, found = fn(t, kh, kl)
            if fault == "half_batch":
                keep = jnp.arange(found.shape[0]) % 2 == 0
                return (jnp.where(keep[:, None], vals, 0.0),
                        found & keep)
            if fault == "altered_answer":
                return vals.at[0, 0].add(1.0), found
            return vals, found
        return wrapped
    if kind == "train":
        # fn(table, tok) -> (successor, rows the lookup returned)
        def step(t, tok):
            if fault == "half_batch":
                keep = jnp.arange(tok.shape[0]) % 2 == 0
                tok = jnp.where(keep, tok, -1)
            t2, rows = fn(t, tok)
            if fault == "unchanged_state":
                return t, rows
            if fault == "altered_answer":
                rows = rows.at[0, 0].add(1.0)
            return t2, rows
        return step
    if kind == "insert":
        # fn(table, tok) -> (successor, status)
        def insert(t, tok):
            if fault == "half_batch":
                keep = jnp.arange(tok.shape[0]) % 2 == 0
                tok = jnp.where(keep, tok, -1)
            t2, status = fn(t, tok)
            if fault == "unchanged_state":
                return t, status
            if fault == "altered_answer":
                status = status.at[0].set(status[0] ^ 1)
            return t2, status
        return insert
    raise ValueError(kind)
