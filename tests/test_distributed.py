"""Distributed-layer tests on an 8-device host mesh (subprocess isolation:
the main test process must keep 1 device for the smoke tests)."""

import json
import subprocess
import sys
import textwrap

import pytest

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
"""


def _run(body: str) -> dict:
    import os

    code = _PRELUDE + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": "src", "XLA_FLAGS": ""},
        cwd="/root/repo",
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_hkv_embedding_roundtrip_and_grads():
    """All-to-all routed table: lookup inserts, serve agrees, grads descend,
    and the result matches a single-device (unsharded) HKV embedding."""
    out = _run("""
    from repro.embedding.dynamic import HKVEmbedding
    from repro.embedding.sparse_opt import SparseOptimizer
    from repro.distributed.table_sharding import ShardedHKVEmbedding
    mesh = make_mesh((2, 4), ("data", "model"))
    emb = HKVEmbedding(capacity=8*128*8, dim=8,
                       optimizer=SparseOptimizer("rowwise_adagrad", lr=0.5))
    semb = ShardedHKVEmbedding(emb=emb, axis_names=("data", "model"))
    state = semb.create_sharded(mesh)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 5000, size=(4, 32)), jnp.int32)

    @jax.jit
    def train_lookup(state, toks):
        return semb.lookup(mesh, state, toks, train=True)

    @jax.jit
    def serve_lookup(state, toks):
        _, rows, _ = semb.lookup(mesh, state, toks, train=False)
        return rows

    @jax.jit
    def grad_apply(state, toks, g):
        return semb.apply_grads(mesh, state, toks, g)

    state, rows, ovf = train_lookup(state, toks)
    served = serve_lookup(state, toks)
    agree = bool(jnp.allclose(rows, served, atol=1e-6))
    # gradient step: pull rows toward 1.0
    target = jnp.ones_like(rows)
    loss0 = float(jnp.mean((rows - target) ** 2))
    g = 2 * (rows - target) / rows.size
    state = grad_apply(state, toks, g)
    rows2 = serve_lookup(state, toks)
    loss1 = float(jnp.mean((rows2 - target) ** 2))
    print(json.dumps({"agree": agree, "overflow": int(ovf),
                      "loss0": loss0, "loss1": loss1}))
    """)
    assert out["agree"]
    assert out["overflow"] == 0
    assert out["loss1"] < out["loss0"]


def test_sharded_lookup_matches_unsharded_init_rows():
    """Deterministic init: sharded cold-start rows == HKVEmbedding defaults."""
    out = _run("""
    from repro.embedding.dynamic import HKVEmbedding
    from repro.distributed.table_sharding import ShardedHKVEmbedding
    mesh = make_mesh((2, 4), ("data", "model"))
    emb = HKVEmbedding(capacity=8*128*8, dim=4)
    semb = ShardedHKVEmbedding(emb=emb, axis_names=("data", "model"))
    state = semb.create_sharded(mesh)
    toks = jnp.asarray(np.arange(16).reshape(2, 8), jnp.int32)

    @jax.jit
    def train_lookup(state, toks):
        return semb.lookup(mesh, state, toks, train=True)

    state, rows, _ = train_lookup(state, toks)
    want = emb.default_rows(emb.keys_of(toks)).reshape(rows.shape)
    print(json.dumps({"match": bool(jnp.allclose(rows, want, atol=1e-6))}))
    """)
    assert out["match"]


def test_compressed_psum_close_to_exact():
    out = _run("""
    from repro.distributed.compression import compressed_psum
    mesh = make_mesh((8,), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 1000)), jnp.float32)

    def body(x):
        return compressed_psum(x, "d")

    y = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                              out_specs=P("d"), check_vma=False))(x)
    exact = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
    err = float(jnp.max(jnp.abs(y - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
    print(json.dumps({"rel_err": err}))
    """)
    assert out["rel_err"] < 0.05  # int8 quantization error bound


def test_error_feedback_accumulates():
    out = _run("""
    from repro.distributed.compression import ef_compress_grads, init_error_state
    mesh = make_mesh((8,), ("d",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(8, 512)), jnp.float32)}

    def body(g):
        e = init_error_state({"w": g["w"]})
        synced, e2 = ef_compress_grads(g, e, "d")
        # second step: error feedback should be non-zero
        return synced["w"], e2["w"]

    s, e = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=({"w": P("d")},),
                                 out_specs=(P("d"), P("d")), check_vma=False))(g)
    exact = jnp.broadcast_to(g["w"].mean(0, keepdims=True), g["w"].shape)
    rel = float(jnp.max(jnp.abs(s - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
    print(json.dumps({"rel": rel, "err_norm": float(jnp.abs(e).sum())}))
    """)
    assert out["rel"] < 0.05
    assert out["err_norm"] > 0  # residual carried


def test_pipeline_matches_sequential():
    out = _run("""
    from repro.distributed.pipeline import pipeline_apply
    mesh = make_mesh((4,), ("pod",))
    rng = np.random.default_rng(2)
    ws = jnp.asarray(rng.normal(size=(4, 16, 16)) / 4.0, jnp.float32)
    xs = jnp.asarray(rng.normal(size=(6, 8, 16)), jnp.float32)  # 6 microbatches

    def stage(w, x):
        return jnp.tanh(x @ w)

    got = pipeline_apply(mesh, "pod", stage, ws, xs)
    want = xs
    for i in range(4):
        want = jnp.tanh(want @ ws[i])
    print(json.dumps({"close": bool(jnp.allclose(got, want, atol=1e-5))}))
    """)
    assert out["close"]


def test_param_specs_cover_every_leaf():
    """Sharding rules must produce valid specs for every arch's params."""
    out = _run("""
    from repro.configs import ARCH_NAMES, get_arch
    from repro.distributed.sharding import param_specs
    from repro.models.lm import CompositeLM
    bad = []
    for name in ARCH_NAMES:
        arch = get_arch(name)
        model = CompositeLM(arch.lm)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_specs(shapes)
        for (pa, leaf), (ps, spec) in zip(
            jax.tree_util.tree_leaves_with_path(shapes),
            jax.tree_util.tree_leaves_with_path(specs),
        ):
            if len([a for a in spec if a is not None]) > leaf.ndim:
                bad.append((name, jax.tree_util.keystr(pa)))
    print(json.dumps({"bad": bad}))
    """)
    assert out["bad"] == []
