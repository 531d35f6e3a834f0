"""GPipe-style pipeline parallelism over the "pod" axis (ppermute ring).

Off by default — the production layout carries DP over pods — but available
for deployments where the inter-pod DCN link cannot sustain full-gradient
all-reduce: pipeline crossing the slow axis moves only activations
(microbatch x d_model per hop) instead of the full gradient set.

Schedule: forward-fill / drain with M microbatches over K stages
(utilization M/(M+K-1)); stage p applies its layer slice then
collective_permute's activations to stage p+1.  Implemented as a shard_map
over the pipeline axis with a static schedule loop — every step is a
(compute, ppermute) pair XLA can overlap.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_apply(mesh, axis: str, stage_fn, stage_params, x_microbatches):
    """stage_fn(params_slice, x) -> x, applied across `axis` stages.

    stage_params: pytree with leading stage axis (sharded over `axis`).
    x_microbatches: [M, mb, ...] microbatched input, replicated per stage.
    Returns the pipeline output [M, mb, ...].
    """
    k = mesh.shape[axis]

    def body(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # my stage's slice
        m = xs.shape[0]
        stage = jax.lax.axis_index(axis)
        n_ticks = m + k - 1
        perm = [(i, (i + 1) % k) for i in range(k)]

        def tick(carry, t):
            buf, out = carry
            # which microbatch enters stage 0 at tick t
            mb_idx = jnp.clip(t, 0, m - 1)
            incoming = jnp.where(stage == 0, 1, 0)
            x_in = jnp.where(incoming, xs[mb_idx], buf)
            active = (t - stage >= 0) & (t - stage < m)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, x_in)
            # last stage writes its finished microbatch to the output slot
            done_idx = jnp.clip(t - (k - 1), 0, m - 1)
            write = active & (stage == k - 1)
            out = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, done_idx, 0),
                lambda o: o,
                out,
            )
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, out), None

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
        # only the last stage holds the real outputs; broadcast via masked psum
        out = jax.lax.psum(jnp.where(stage == k - 1, out, 0.0), axis)
        return out

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),  # microbatches replicated across stages
    )
    return shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )(stage_params, x_microbatches)
