"""The zipf generator draws the power law's mass at the head and in the
tail, and its tail ranks stay distinct."""

import jax
import numpy as np
import pytest

from hkvbench import keys as keymod

M, ALPHA = 1 << 28, 1.05


@pytest.fixture(scope="module")
def ranks():
    r = keymod.zipf_ranks(jax.random.key(7), 1 << 20, ALPHA, M)
    return np.asarray(r).astype(np.int64)


def _mass(lo, hi):
    """P(lo <= rank < hi) under the continuous power law."""
    e, top = 1.0 - ALPHA, float(M + 1) ** (1.0 - ALPHA)
    cdf = lambda x: (x ** e - 1.0) / (top - 1.0)
    return cdf(hi + 1.0) - cdf(lo + 1.0)


@pytest.mark.parametrize("lo,hi", [(0, 1), (10, 100), (1000, 10000),
                                   (10**5, 10**6), (1 << 24, M)])
def test_zipf_mass(ranks, lo, hi):
    p = _mass(lo, hi)
    sd = np.sqrt(p * (1 - p) / len(ranks))
    assert abs(np.mean((ranks >= lo) & (ranks < hi)) - p) < 5 * sd


def test_zipf_tail_ranks_stay_distinct(ranks):
    tail = ranks[ranks >= 1 << 24]
    assert 0 <= ranks.min() and ranks.max() < M
    assert len(np.unique(tail)) > 0.99 * len(tail)

