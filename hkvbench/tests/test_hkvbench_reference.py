"""The device reference the train cell is checked against follows the
plain numpy model of the table step for step: the same rows back, the
same keys in every slot, the same rows held."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hkvbench import keys as keymod, reference

SEED = 2**31 + 23
CONFIG = {"capacity": 8 * 128, "slots_per_bucket": 128, "dim": 8,
          "buckets_per_key": 2}
RANKS = 2 * 8 * 128


def _steps(n_steps=3, n=1024):
    c = jnp.uint32(keymod.offset(SEED))
    out = []
    for i in range(n_steps):
        k = jax.random.fold_in(keymod.prng_key(SEED, 2), i)
        r = keymod.zipf_ranks(k, n, 1.05, 4 * RANKS)
        out.append(np.asarray(keymod.token(r, c)).astype(np.int64))
    return out


@pytest.mark.parametrize("dual", [True, False])
def test_device_steps_follow_the_numpy_model(dual):
    config = dict(CONFIG, buckets_per_key=2 if dual else 1)
    ref = reference.RefTable.loaded(SEED, config, RANKS)
    keys, scores, rows, clock = reference.train_state(SEED, config, RANKS)
    np.testing.assert_array_equal(np.asarray(keys), ref.keys)
    evicted = 0
    for i, tok in enumerate(_steps()):
        want, status = ref.find_or_insert(
            tok, reference.init_rows_np(tok, config["dim"]))
        ref.apply_rowwise_adagrad(tok, want, 0.01, 1e-10)
        evicted += int(np.sum(status == reference.EVICTED))
        keys, scores, rows, got = reference.train_step_device(
            keys, scores, rows, jnp.asarray(tok.astype(np.int32)),
            jnp.int32(clock + 1 + i), dual, 0.01, 1e-10)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                   atol=1e-7)
        np.testing.assert_array_equal(np.asarray(keys), ref.keys)
    assert evicted > 0
    held = ref.keys[ref.keys >= 0]
    h, b, s = reference.locate_device(keys, jnp.asarray(held.astype(np.int32)),
                                      dual)
    assert np.asarray(h).all()
    np.testing.assert_allclose(np.asarray(rows[b, s]), ref.rows(held),
                               rtol=1e-6, atol=1e-7)
