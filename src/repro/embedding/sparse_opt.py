"""Sparse optimizers for HKV-backed embeddings (updater-role gradient path).

Optimizer slot state is colocated with each embedding row as aux value
columns (HugeCTR-style): a table row is [embedding dim | aux columns], so
an eviction carries the optimizer state away with the row and an admission
starts fresh — no separate slot-state table to keep consistent.

  rowwise_adagrad — 1 aux column: the row-wise accumulated squared-grad
                    mean (the DLRM production standard).
  adagrad         — `dim` aux columns: per-coordinate accumulator.
  sgd / sgdm      — 0 / `dim` aux columns.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def _rounded(p: jax.Array) -> jax.Array:
    """Pin ``p`` (a float product) to its own IEEE rounding.

    FMA contraction folds ``a*b + c`` into one rounding, and whether the
    compiler contracts depends on the fusion context around the expression
    — the identical formula can produce last-ULP-different results in two
    programs (the fused update kernel vs the batched jnp reference).
    ``llvm.fmuladd`` formation requires the multiply to have a SINGLE use,
    so giving the product a second, value-preserving use (``p - p`` cannot
    be folded to zero without fast-math: NaN/inf operands) forces the
    product to round separately in every context.  ``lax
    .optimization_barrier`` does NOT work for this — XLA:CPU strips it
    before LLVM sees the loop.  Cost: two extra vector ops per site.
    """
    return p + (p - p)


def _mean_sq(col, dim: int) -> jax.Array:
    """Mean of the squares of columns col(0..dim-1), summed left to right.

    The order is explicit because a reduction's order is the compiler's
    choice and differs between a [1, dim] row and an [N, dim] batch; an
    unrolled chain of rounded products rounds alike in both.
    """
    total = _rounded(col(0) * col(0))
    for j in range(1, dim):
        total = total + _rounded(col(j) * col(j))
    return total / dim


@dataclasses.dataclass(frozen=True)
class SparseOptimizer:
    name: str = "rowwise_adagrad"
    lr: float = 0.01
    eps: float = 1e-10
    momentum: float = 0.9

    def aux_dim(self, dim: int) -> int:
        return {"sgd": 0, "sgdm": dim, "rowwise_adagrad": 1, "adagrad": dim}[self.name]

    def apply(self, rows: jax.Array, grads: jax.Array, dim: int) -> jax.Array:
        """rows: [N, dim + aux] gathered table rows; grads: [N, dim].

        Returns updated rows (embedding + refreshed aux columns) — written
        back through the updater role (`assign`), never structurally.

        Every multiply feeding an add/sub is pinned to one IEEE rounding
        via ``_rounded``: XLA/LLVM otherwise contract mul+add into an FMA
        *depending on the surrounding fusion context*, so the same row
        would round differently inside the fused update kernel ([1, V]
        slices) than in the batched jnp reference — and the repo's
        acceptance bar for kernels is BIT-identity, not allclose.
        """
        emb, aux = rows[:, :dim], rows[:, dim:]
        g = grads.astype(emb.dtype)
        rnd = _rounded
        if self.name == "sgd":
            return emb - rnd(self.lr * g)
        if self.name == "sgdm":
            m = rnd(self.momentum * aux) + g
            return jnp.concatenate([emb - rnd(self.lr * m), m], axis=1)
        if self.name == "rowwise_adagrad":
            acc = aux[:, 0] + _mean_sq(lambda j: g[:, j], dim)
            step = self.lr / (jnp.sqrt(acc) + self.eps)
            return jnp.concatenate(
                [emb - rnd(step[:, None] * g), acc[:, None]], axis=1)
        if self.name == "adagrad":
            acc = aux + rnd(g * g)
            return jnp.concatenate(
                [emb - rnd(self.lr * g / (jnp.sqrt(acc) + self.eps)), acc],
                axis=1,
            )
        raise ValueError(self.name)

    def apply_lanes(self, x: jax.Array, g: jax.Array, dim: int,
                    roll) -> jax.Array:
        """`apply` on one row laid out along the lanes of a vector row.

        x [1, L] holds [embedding | aux] at lanes [0, dim + aux); g [1, L]
        the gradient at lanes [0, dim) (zeros beyond); lanes past the row
        come back unchanged.  `roll(v, shift)` rotates lanes (the kernel's
        `pltpu.roll`).  Every lane computes `apply`'s own formula, so a
        kernel row and the batched reference agree bitwise.
        """
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        lanes = x.shape[1]
        emb_lane = lane < dim
        g = g.astype(x.dtype)
        rnd = _rounded
        if self.name == "sgd":
            return jnp.where(emb_lane, x - rnd(self.lr * g), x)
        if self.name == "rowwise_adagrad":
            aux0 = jnp.sum(jnp.where(lane == dim, x, 0), axis=1, keepdims=True)
            col = lambda j: jnp.sum(jnp.where(lane == j, g, 0), axis=1,
                                    keepdims=True)
            acc = aux0 + _mean_sq(col, dim)
            step = self.lr / (jnp.sqrt(acc) + self.eps)
            out = jnp.where(lane == dim, acc, x)
            return jnp.where(emb_lane, x - rnd(step * g), out)
        aux = roll(x, lanes - dim)          # aux lane dim+j -> lane j
        if self.name == "sgdm":
            m = rnd(self.momentum * aux) + g
            emb, new_aux = x - rnd(self.lr * m), m
        elif self.name == "adagrad":
            new_aux = aux + rnd(g * g)
            emb = x - rnd(self.lr * g / (jnp.sqrt(new_aux) + self.eps))
        else:
            raise ValueError(self.name)
        out = jnp.where((lane >= dim) & (lane < 2 * dim),
                        roll(new_aux, dim), x)
        return jnp.where(emb_lane, emb, out)

