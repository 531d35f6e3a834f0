"""The plain reference: the table's semantics restated without the program.

Nothing here imports `repro`.  It restates, from the paper's description
and the benchmark's own load rule (`build.py`'s docstring), what a correct
table answers:

  * the hash: two coupled Murmur3 fmix32 passes over the 64-bit key give
    h1 (primary bucket = h1 mod B, digest = top byte of h1) and h2
    (secondary bucket = h2 mod B);
  * which ranks the loaded table holds, and in which bucket and slot;
  * the row stored for a token.

Residency is computed by counting, for each queried rank, the earlier
ranks that land in the same bucket (a different algorithm from
`build.py`'s chunked placement), with the hash evaluated in plain `jax.numpy`
on the device and the counting in numpy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_MASK = 0x7FFFFFFF
MULT = 0x9E3779B1
MULT_INV = pow(MULT, -1, 1 << 31)
ROW_SALT = 0x9E37
_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35
_GOLDEN, _SALT2 = 0x9E3779B9, 0x7FEB352D


def offset(seed: int) -> int:
    return (int(seed) * 0x85EBCA77) & TOKEN_MASK


def token_np(rank, c: int) -> np.ndarray:
    r = np.asarray(rank, np.uint64)
    return ((r * np.uint64(MULT) + np.uint64(c)) & np.uint64(TOKEN_MASK)
            ).astype(np.int64)


def rank_np(tok, c: int) -> np.ndarray:
    """Inverse of `token_np`."""
    t = np.asarray(tok, np.uint64)
    d = (t + np.uint64(1 << 31) - np.uint64(c)) & np.uint64(TOKEN_MASK)
    return ((d * np.uint64(MULT_INV)) & np.uint64(TOKEN_MASK)).astype(np.int64)


def rows_np(tok, dim: int) -> np.ndarray:
    """The row loaded for each token: 16-bit patterns as multiples of
    2^-15 in [-1, 1)."""
    t = np.asarray(tok, np.uint64)[:, None]
    j = np.arange(dim, dtype=np.uint64)[None, :]
    bits = (t ^ (j * np.uint64(ROW_SALT))) & np.uint64(0xFFFF)
    return ((bits.astype(np.int64) - 32768) / 32768.0).astype(np.float32)


def _fmix(xp, h):
    u = xp.uint32
    h = h ^ (h >> u(16))
    h = h * u(_C1)
    h = h ^ (h >> u(13))
    h = h * u(_C2)
    return h ^ (h >> u(16))


def hash_pair(xp, hi, lo):
    """(h1, h2) of keys given as uint32 planes, in numpy or jax.numpy."""
    u = xp.uint32
    a = _fmix(xp, hi ^ u(_GOLDEN))
    b = _fmix(xp, lo ^ u(_SALT2))
    return _fmix(xp, a ^ lo), _fmix(xp, b ^ hi ^ u(_GOLDEN))


def landing_np(rank, c: int, num_buckets: int, dual: bool) -> np.ndarray:
    rank = np.asarray(rank, np.int64)
    lo = token_np(rank, c).astype(np.uint32)
    with np.errstate(over="ignore"):
        h1, h2 = hash_pair(np, np.zeros_like(lo), lo)
    b = (h1 % np.uint32(num_buckets)).astype(np.int64)
    if dual:
        b2 = (h2 % np.uint32(num_buckets)).astype(np.int64)
        b = np.where(rank & 1 == 1, b2, b)
    return b


def _marked_ranks(c: int, num_buckets: int, dual: bool, n_ranks: int,
                  buckets: np.ndarray, chunk: int = 1 << 24):
    """(rank, landing bucket) of every rank < n_ranks that lands in one of
    `buckets`: the hash over all ranks runs in jax.numpy on the device."""
    mark = np.zeros(num_buckets, bool)
    mark[buckets] = True
    # at most len(buckets) of the buckets are marked: the buffer's size
    # (and so the compiled program) depends on the sample size alone
    cap = int(chunk * min(1.0, len(buckets) / num_buckets) * 1.5) + 4096

    @jax.jit
    def land(mark, start, cc):
        r = start + jnp.arange(chunk, dtype=jnp.uint32)
        lo = (r * jnp.uint32(MULT) + cc) & jnp.uint32(TOKEN_MASK)
        h1, h2 = hash_pair(jnp, jnp.zeros_like(lo), lo)
        b = (h1 % jnp.uint32(num_buckets)).astype(jnp.int32)
        if dual:
            b2 = (h2 % jnp.uint32(num_buckets)).astype(jnp.int32)
            b = jnp.where((r & 1) == 1, b2, b)
        m = mark[b] & (r < jnp.uint32(n_ranks))
        idx = jnp.nonzero(m, size=cap, fill_value=chunk)[0]
        return idx, b[jnp.clip(idx, 0, chunk - 1)], jnp.sum(m)

    ranks, lands = [], []
    mark = jnp.asarray(mark)
    for start in range(0, n_ranks, chunk):
        idx, b, count = land(mark, jnp.uint32(start), jnp.uint32(c))
        idx, b, count = np.asarray(idx), np.asarray(b), int(count)
        if count > len(idx):
            raise RuntimeError("reference: marked ranks overflow their buffer")
        ranks.append(idx[:count].astype(np.int64) + start)
        lands.append(b[:count].astype(np.int64))
    return np.concatenate(ranks), np.concatenate(lands)


def resident_slot(rank: np.ndarray, c: int, num_buckets: int,
                  slots: int, dual: bool, n_ranks: int) -> np.ndarray:
    """Slot of each rank in the loaded table, -1 where it is not resident.

    A rank is resident iff it was drawn (rank < n_ranks) and fewer than
    `slots` earlier ranks landed in its bucket; its slot is that count."""
    rank = np.asarray(rank, np.int64)
    land = landing_np(rank, c, num_buckets, dual)
    r_all, b_all = _marked_ranks(c, num_buckets, dual, n_ranks, land)
    key = np.sort((b_all << 32) | r_all)
    before = (np.searchsorted(key, (land << 32) | rank)
              - np.searchsorted(key, land << 32))
    ok = (rank < n_ranks) & (before < slots)
    return np.where(ok, before, -1)


# ---------------------------------------------------------------------------
# The table as a plain model: keys and scores per slot, rows of the keys
# held.  Batch semantics (the paper's Algorithms 2 and 3 in batch form):
#   * one clock tick per batch; keys already held are hits: their score
#     becomes the clock (LRU; duplicates touch once) and, for
#     insert_or_assign, their row becomes the batch's last row for them;
#   * each missing key picks a target bucket: while either candidate has
#     a free slot the less occupied (ties: primary), else the one with
#     the lower minimum score (ties: primary), judged after the hits;
#   * per target, missing keys in ascending key order are paired with the
#     bucket's slots weakest first (empty first by slot, then ascending
#     score, key, slot); the r-th key is admitted iff its slot is empty or
#     its score (the clock) beats the slot's strictly, else rejected.
# ---------------------------------------------------------------------------

UPDATED, INSERTED, EVICTED, REJECTED = 1, 2, 3, 4


def loaded_rank_plane(c: int, num_buckets: int, slots: int, dual: bool,
                      n_ranks: int):
    """[B, S] rank held by each slot of the loaded table (-1: empty), by
    one stable sort of all drawn ranks on their landing bucket (a device
    array)."""
    @jax.jit
    def plane(cc):
        r = jnp.arange(n_ranks, dtype=jnp.uint32)
        lo = (r * jnp.uint32(MULT) + cc) & jnp.uint32(TOKEN_MASK)
        h1, h2 = hash_pair(jnp, jnp.zeros_like(lo), lo)
        land = (h1 % jnp.uint32(num_buckets)).astype(jnp.int32)
        if dual:
            land2 = (h2 % jnp.uint32(num_buckets)).astype(jnp.int32)
            land = jnp.where((r & 1) == 1, land2, land)
        order = jnp.argsort(land, stable=True).astype(jnp.int32)
        count = jnp.zeros((num_buckets,), jnp.int32).at[land].add(1)
        first = jnp.cumsum(count) - count
        s = jnp.arange(slots, dtype=jnp.int32)[None, :]
        idx = jnp.clip(first[:, None] + s, 0, n_ranks - 1)
        return jnp.where(s < count[:, None], order[idx], -1)

    return plane(jnp.uint32(c))


class RefTable:
    """Keys (token, -1 empty) and LRU scores per slot, and the rows of the
    keys held: loaded keys keep their loaded row until a batch writes it."""

    def __init__(self, keys, scores, clock, dim: int, width: int, dual: bool):
        self.keys, self.scores, self.clock = keys, scores, clock
        self.dim, self.width, self.dual = dim, width, dual
        self.nb, self.slots = keys.shape
        self._tok = np.zeros(0, np.int64)       # sorted tokens with rows
        self._rows = np.zeros((0, width), np.float32)

    @classmethod
    def loaded(cls, seed: int, config: dict, n_ranks: int) -> "RefTable":
        c = offset(seed)
        slots = int(config["slots_per_bucket"])
        nb = int(config["capacity"]) // slots
        dual = int(config["buckets_per_key"]) == 2
        rank = np.asarray(loaded_rank_plane(c, nb, slots, dual, n_ranks)
                          ).astype(np.int64)
        held = rank >= 0
        keys = np.where(held, token_np(np.maximum(rank, 0), c), -1)
        scores = np.where(held, n_ranks - rank, 0)
        dim = int(config["dim"])
        return cls(keys, scores, n_ranks + 1, dim, dim + 1, dual)

    # -- rows ---------------------------------------------------------------

    def rows(self, tok) -> np.ndarray:
        """[n, width] rows of held keys: written rows, else loaded ones."""
        tok = np.asarray(tok, np.int64)
        out = np.zeros((len(tok), self.width), np.float32)
        out[:, :self.dim] = rows_np(tok, self.dim)
        if len(self._tok):
            i = np.minimum(np.searchsorted(self._tok, tok), len(self._tok) - 1)
            written = self._tok[i] == tok
            out[written] = self._rows[i[written]]
        return out

    def _write(self, tok, rows):
        keep = ~np.isin(self._tok, tok)
        t = np.concatenate([self._tok[keep], tok])
        r = np.concatenate([self._rows[keep], rows])
        o = np.argsort(t, kind="stable")
        self._tok, self._rows = t[o], r[o]

    def _drop(self, tok):
        keep = ~np.isin(self._tok, tok)
        self._tok, self._rows = self._tok[keep], self._rows[keep]

    # -- probing --------------------------------------------------------------

    def candidates(self, tok):
        lo = np.asarray(tok, np.int64).astype(np.uint32)
        with np.errstate(over="ignore"):
            h1, h2 = hash_pair(np, np.zeros_like(lo), lo)
        b1 = (h1 % np.uint32(self.nb)).astype(np.int64)
        b2 = ((h2 % np.uint32(self.nb)).astype(np.int64) if self.dual
              else b1)
        return b1, b2

    def locate(self, tok):
        """(held, bucket, slot) of each token."""
        tok = np.asarray(tok, np.int64)
        b1, b2 = self.candidates(tok)
        m1 = self.keys[b1] == tok[:, None]
        m2 = self.keys[b2] == tok[:, None]
        in1 = m1.any(1)
        held = in1 | m2.any(1)
        bucket = np.where(in1, b1, b2)
        slot = np.where(in1, m1.argmax(1), m2.argmax(1))
        return held, bucket, slot

    # -- the batch ops --------------------------------------------------------

    def upsert(self, tok, init_rows, *, write_hits: bool):
        """insert_or_assign (write_hits) or find_or_insert of one batch of
        tokens (-1: padding) with their rows [n, dim].  Returns per lane
        (status, held after the op) and the evicted tokens."""
        tok = np.asarray(tok, np.int64)
        init_rows = np.asarray(init_rows, np.float32)
        n = len(tok)
        self.clock += 1
        clock = self.clock
        valid = tok >= 0
        u, inv = np.unique(tok[valid], return_inverse=True)
        lane = np.flatnonzero(valid)
        last = np.zeros(len(u), np.int64)
        last[inv] = lane                       # the batch's last writer
        held, hb, hs = self.locate(u)
        self.scores[hb[held], hs[held]] = clock
        status_u = np.where(held, UPDATED, 0)
        if write_hits and held.any():
            rows = np.zeros((int(held.sum()), self.width), np.float32)
            rows[:, :self.dim] = init_rows[last[held]]
            rows[:, self.dim:] = self.rows(u[held])[:, self.dim:]
            self._write(u[held], rows)
        # misses: target bucket, judged after the hits
        m = ~held
        mu, mlast = u[m], last[m]
        b1, b2 = self.candidates(mu)
        live1, live2 = self.keys[b1] >= 0, self.keys[b2] >= 0
        o1, o2 = live1.sum(1), live2.sum(1)
        big = np.iinfo(np.int64).max
        n1 = np.where(live1, self.scores[b1], big).min(1)
        n2 = np.where(live2, self.scores[b2], big).min(1)
        free = (o1 < self.slots) | (o2 < self.slots)
        target = np.where(free, np.where(o2 < o1, b2, b1),
                          np.where(n2 < n1, b2, b1))
        order = np.lexsort((mu, target))
        t_s, k_s, l_s = target[order], mu[order], mlast[order]
        idx = np.arange(len(t_s))
        start = np.r_[True, t_s[1:] != t_s[:-1]][:len(t_s)]
        rank = idx - np.maximum.accumulate(np.where(start, idx, 0))
        tb, ti = np.unique(t_s, return_inverse=True)
        kk, ss = self.keys[tb], self.scores[tb]
        live = kk >= 0
        slot_ix = np.broadcast_to(np.arange(self.slots), kk.shape)
        vorder = np.lexsort((slot_ix, np.where(live, kk, 0),
                             np.where(live, ss, 0), live), axis=-1)
        v = vorder[ti, np.minimum(rank, self.slots - 1)]
        vlive, vscore = live[ti, v], ss[ti, v]
        admit = (rank < self.slots) & (~vlive | (clock > vscore))
        evicted = kk[ti, v][admit & vlive]
        self.keys[t_s[admit], v[admit]] = k_s[admit]
        self.scores[t_s[admit], v[admit]] = clock
        self._drop(evicted)
        rows = np.zeros((int(admit.sum()), self.width), np.float32)
        rows[:, :self.dim] = init_rows[l_s[admit]]
        self._write(k_s[admit], rows)
        st_m = np.where(admit, np.where(vlive, EVICTED, INSERTED), REJECTED)
        miss_status = np.zeros(len(mu), np.int64)
        miss_status[order] = st_m
        status_u[m] = miss_status
        status = np.zeros(n, np.int64)
        status[lane] = status_u[inv]
        held_after = np.isin(status, (UPDATED, INSERTED, EVICTED))
        return status, held_after, evicted

    def find_or_insert(self, tok, init_rows):
        """find_or_insert: rows [n, dim] each lane gets back (its stored row,
        or its init row where the key was rejected) and the lane status."""
        tok = np.asarray(tok, np.int64)
        status, held, _ = self.upsert(tok, init_rows, write_hits=False)
        out = np.asarray(init_rows, np.float32).copy()
        out[held] = self.rows(tok[held])[:, :self.dim]
        return out, status

    def apply_rowwise_adagrad(self, tok, grads, lr: float, eps: float,
                              dtype=np.float32):
        """Sum the gradients per key; rowwise adagrad on each key held."""
        tok = np.asarray(tok, np.int64)
        valid = tok >= 0
        u, inv = np.unique(tok[valid], return_inverse=True)
        g = np.zeros((len(u), self.dim), np.float32)
        np.add.at(g, inv, np.asarray(grads, np.float32)[valid])
        held, _, _ = self.locate(u)
        u, g = u[held], g[held].astype(dtype)
        rows = self.rows(u).astype(dtype)
        emb, acc = rows[:, :self.dim], rows[:, self.dim]
        sq = np.zeros(len(u), dtype)
        for j in range(self.dim):
            sq = (sq + g[:, j] * g[:, j]).astype(dtype)
        acc = (acc + sq / dtype(self.dim)).astype(dtype)
        step = (dtype(lr) / (np.sqrt(acc) + dtype(eps))).astype(dtype)
        new = np.zeros((len(u), self.width), np.float32)
        new[:, :self.dim] = (emb - step[:, None] * g).astype(np.float32)
        new[:, self.dim] = acc.astype(np.float32)
        self._write(u, new)


def init_rows_np(tok, dim: int) -> np.ndarray:
    """The row a training lookup admits a new key with: per column, an
    fmix32 of h1 and a column salt, as a uniform draw in +-1/sqrt(dim)."""
    lo = np.asarray(tok, np.int64).astype(np.uint32)
    with np.errstate(over="ignore"):
        h1, _ = hash_pair(np, np.zeros_like(lo), lo)
        salt = ((np.arange(dim, dtype=np.uint32) * np.uint32(_GOLDEN))
                ^ np.uint32(_C1))
        bits = _fmix(np, h1[:, None] ^ salt[None, :])
    uni = bits.astype(np.float32) * np.float32(1.0 / 4294967296.0)
    return ((uni - np.float32(0.5)) * np.float32(2.0 / np.sqrt(dim))
            ).astype(np.float32)


# ---------------------------------------------------------------------------
# The same batch semantics over the whole table on the device, for batches
# of distinct keys (`upsert_device`): the tables of the ingest cells are
# too large for numpy to replay a window of 2^20-key batches in time.
# Keys are tokens (int32, -1 empty) and scores int32.
# ---------------------------------------------------------------------------

def _stable_order(keys_minor_first):
    """Permutation sorting along the last axis by several keys (the LAST
    most significant), ties kept in index order: one stable sort per key."""
    perm = None
    for k in keys_minor_first:
        if perm is None:
            iota = jax.lax.broadcasted_iota(jnp.int32, k.shape, k.ndim - 1)
            _, perm = jax.lax.sort((k, iota), dimension=k.ndim - 1,
                                   num_keys=1, is_stable=True)
        else:
            kp = jnp.take_along_axis(k, perm, axis=-1)
            _, perm = jax.lax.sort((kp, perm), dimension=k.ndim - 1,
                                   num_keys=1, is_stable=True)
    return perm


def device_state(seed: int, config: dict, n_ranks: int):
    """(keys, scores, clock) of the loaded table as device arrays."""
    c = offset(seed)
    slots = int(config["slots_per_bucket"])
    nb = int(config["capacity"]) // slots
    dual = int(config["buckets_per_key"]) == 2
    rank = loaded_rank_plane(c, nb, slots, dual, n_ranks)
    held = rank >= 0
    r = jnp.maximum(rank, 0).astype(jnp.uint32)
    tok = ((r * jnp.uint32(MULT) + jnp.uint32(c)) & jnp.uint32(TOKEN_MASK)
           ).astype(jnp.int32)
    keys = jnp.where(held, tok, -1)
    scores = jnp.where(held, n_ranks - rank, 0).astype(jnp.int32)
    return keys, scores, n_ranks + 1


def _candidates(tok, nb: int, dual: bool):
    lo = tok.astype(jnp.uint32)
    h1, h2 = hash_pair(jnp, jnp.zeros_like(lo), lo)
    b1 = (h1 % jnp.uint32(nb)).astype(jnp.int32)
    return b1, ((h2 % jnp.uint32(nb)).astype(jnp.int32) if dual else b1)


def _split(a, chunk):
    return a.reshape((a.shape[0] // chunk, chunk) + a.shape[1:])


def _locate(keys, tok, dual, chunk):
    b1, b2 = _candidates(tok, keys.shape[0], dual)

    def one(args):
        t, x1, x2 = args
        m1 = keys[x1] == t[:, None]
        m2 = keys[x2] == t[:, None]
        return (m1.any(1), m2.any(1), jnp.argmax(m1, 1).astype(jnp.int32),
                jnp.argmax(m2, 1).astype(jnp.int32))

    n = tok.shape[0]
    in1, in2, s1, s2 = (a.reshape(n) for a in jax.lax.map(
        one, (_split(tok, chunk), _split(b1, chunk), _split(b2, chunk))))
    return ((in1 | in2) & (tok >= 0), jnp.where(in1, b1, b2),
            jnp.where(in1, s1, s2), b1, b2)


@functools.partial(jax.jit, static_argnums=(2, 3))
def held_device(keys, tok, dual: bool, chunk: int = 1 << 12):
    """Whether the table holds each token (int32, length a multiple of
    `chunk`)."""
    return _locate(keys, tok, dual, chunk)[0]


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(0, 1))
def upsert_device(keys, scores, tok, clock, dual: bool, chunk: int = 1 << 14):
    """insert_or_assign of distinct tokens (-1: padding; length a multiple
    of `chunk`): the successor (keys, scores) and each lane's status."""
    nb, slots = keys.shape
    n = tok.shape[0]
    valid = tok >= 0
    held, hb, hs, b1, b2 = _locate(keys, tok, dual, chunk)
    scores = scores.at[jnp.where(held, hb, nb), hs].set(clock, mode="drop")
    big = jnp.iinfo(jnp.int32).max

    def stats(args):
        out = []
        for x in args:
            live = keys[x] >= 0
            out += [jnp.sum(live, 1),
                    jnp.min(jnp.where(live, scores[x], big), 1)]
        return tuple(out)

    o1, n1, o2, n2 = (a.reshape(n) for a in jax.lax.map(
        stats, (_split(b1, chunk), _split(b2, chunk))))
    free = (o1 < slots) | (o2 < slots)
    target = jnp.where(free, jnp.where(o2 < o1, b2, b1),
                       jnp.where(n2 < n1, b2, b1))
    tkey = jnp.where(valid & ~held, target, nb)
    perm = _stable_order([tok, tkey])
    t_s, k_s = tkey[perm], tok[perm]
    iota = jnp.arange(n, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), t_s[1:] != t_s[:-1]])
    rank = iota - jax.lax.cummax(jnp.where(first, iota, 0))

    def victim(args):
        t, r = args
        row = jnp.clip(t, 0, nb - 1)
        kk, ss = keys[row], scores[row]
        live = kk >= 0
        order = _stable_order([jnp.where(live, kk, 0),
                               jnp.where(live, ss, 0),
                               live.astype(jnp.int32)])
        v = jnp.take_along_axis(order, jnp.clip(r, 0, slots - 1)[:, None],
                                axis=1)
        take = lambda a: jnp.take_along_axis(a, v, axis=1)[:, 0]
        return v[:, 0], take(live), take(ss)

    v, vlive, vscore = (a.reshape(n) for a in jax.lax.map(
        victim, (_split(t_s, chunk), _split(rank, chunk))))
    admit = (t_s < nb) & (rank < slots) & (~vlive | (clock > vscore))
    row = jnp.where(admit, t_s, nb)
    keys = keys.at[row, v].set(k_s, mode="drop")
    scores = scores.at[row, v].set(clock, mode="drop")
    st_s = jnp.where(admit, jnp.where(vlive, EVICTED, INSERTED),
                     jnp.where(t_s < nb, REJECTED, 0))
    status = jnp.zeros((n,), jnp.int32).at[perm].set(st_s)
    return keys, scores, jnp.where(held, UPDATED, status)


# ---------------------------------------------------------------------------
# A training step over the whole table on the device (`train_step_device`):
# find_or_insert of the step's tokens with the batch semantics above
# (`upsert_device` on the distinct tokens), the row each lane gets back
# (its stored row, or its init row where the key was rejected), then the
# gradient of 0.5 |row|^2 summed per key and rowwise adagrad on every key
# held.  Rows live in a [B, S, dim + 1] plane beside the keys: the
# embedding, then the adagrad accumulator.
# ---------------------------------------------------------------------------

def _loaded_rows(tok, dim: int):
    """`rows_np` on the device: [..., dim] for int32 tokens."""
    t = tok.astype(jnp.uint32)[..., None]
    j = jnp.arange(dim, dtype=jnp.uint32)
    bits = (t ^ (j * jnp.uint32(ROW_SALT))) & jnp.uint32(0xFFFF)
    return (bits.astype(jnp.int32) - 32768).astype(jnp.float32) * (
        1.0 / 32768)


def _init_rows(tok, dim: int):
    """`init_rows_np` on the device."""
    lo = tok.astype(jnp.uint32)
    h1, _ = hash_pair(jnp, jnp.zeros_like(lo), lo)
    salt = ((jnp.arange(dim, dtype=jnp.uint32) * jnp.uint32(_GOLDEN))
            ^ jnp.uint32(_C1))
    bits = _fmix(jnp, h1[:, None] ^ salt[None, :])
    uni = bits.astype(jnp.float32) * jnp.float32(1.0 / 4294967296.0)
    return (uni - jnp.float32(0.5)) * jnp.float32(2.0 / np.sqrt(dim))


def train_state(seed: int, config: dict, n_ranks: int):
    """(keys, scores, rows, clock) of the loaded table on the device."""
    keys, scores, clock = device_state(seed, config, n_ranks)
    dim = int(config["dim"])

    @jax.jit
    def rows_of(keys):
        row = _loaded_rows(keys, dim)
        acc = jnp.zeros(keys.shape + (1,), jnp.float32)
        return jnp.where((keys >= 0)[..., None],
                         jnp.concatenate([row, acc], -1), 0.0)

    return keys, scores, rows_of(keys), clock


@functools.partial(jax.jit, static_argnums=(2, 3))
def locate_device(keys, tok, dual: bool, chunk: int = 1 << 12):
    """(held, bucket, slot) of each int32 token."""
    n = tok.shape[0]
    pad = jnp.full(((-n) % chunk,), -1, tok.dtype)
    held, bucket, slot, _, _ = _locate(keys, jnp.concatenate([tok, pad]),
                                       dual, chunk)
    return held[:n], bucket[:n], slot[:n]


@functools.partial(jax.jit, static_argnums=(5, 6, 7),
                   donate_argnums=(0, 1, 2))
def train_step_device(keys, scores, rows, tok, clock, dual: bool, lr: float,
                      eps: float):
    """One step for int32 tokens (duplicates allowed; length a multiple of
    2^14 or shorter): the successor (keys, scores, rows) and the [n, dim]
    rows the lookup returned per lane."""
    nb = keys.shape[0]
    n = tok.shape[0]
    dim = rows.shape[-1] - 1
    u, inv = jnp.unique(tok, size=n, fill_value=-1, return_inverse=True)
    inv = inv.reshape(n)
    chunk = min(n, 1 << 14)
    keys, scores, status = upsert_device(keys, scores, u, clock, dual, chunk)
    held, hb, hs, _, _ = _locate(keys, u, dual, chunk)
    init = _init_rows(u, dim)
    new = (status == INSERTED) | (status == EVICTED)
    blank = jnp.concatenate([init, jnp.zeros((n, 1), jnp.float32)], 1)
    # an admitted key starts from its init row, not its victim's
    got = jnp.where(new[:, None], blank, rows[hb, hs])
    back = jnp.where(held[:, None], got[:, :dim], init)
    g = jax.ops.segment_sum(back[inv], inv, num_segments=n)
    acc = got[:, dim] + jnp.sum(g * g, axis=1) / dim
    step = lr / (jnp.sqrt(acc) + eps)
    upd = jnp.concatenate([got[:, :dim] - step[:, None] * g, acc[:, None]],
                          1)
    rows = rows.at[jnp.where(held, hb, nb), hs].set(upd, mode="drop")
    return keys, scores, rows, back[inv]
