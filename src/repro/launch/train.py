"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 20 --backend hkv --ckpt-dir runs/ckpt

On the dev container this runs the REDUCED config on a small host mesh
(--smoke); on a TPU slice the same script runs the full config on the
production mesh (jax.distributed.initialize is invoked when the
environment advertises multi-host).
"""

from __future__ import annotations

import argparse
import os

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--backend", choices=("dense", "hkv"), default="dense")
    ap.add_argument("--hkv-hot-capacity", type=int, default=None,
                    help="run the HKV table as a two-tier hierarchy: this "
                    "many hot slots in front of the cold table, both in "
                    "device memory (a sharded table cannot hold a host-"
                    "resident plane) — DESIGN.md §2.5; requires --backend "
                    "hkv")
    ap.add_argument("--optimizer", choices=("adamw", "adamw8bit", "adafactor", "sgdm"),
                    default="adamw")
    ap.add_argument("--ckpt-dir", default="runs/ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if os.environ.get("REPRO_MULTIHOST"):
        import jax

        jax.distributed.initialize()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.launch import compile_cache

    compile_cache.enable()

    from repro.configs import get_arch
    from repro.data import DataCursor, HostPrefetcher, TokenStream
    from repro.distributed.table_sharding import ShardedHKVTable
    from repro.embedding.dynamic import HKVEmbedding
    from repro.embedding.sparse_opt import SparseOptimizer
    from repro.launch.mesh import make_dev_mesh
    from repro.optim import adafactor, adamw, adamw8bit, sgdm
    from repro.train.driver import TrainDriver
    from repro.train.step import StepBuilder

    arch = get_arch(args.arch)
    lm = arch.smoke if args.smoke else arch.lm
    if args.backend == "hkv":
        lm = dataclasses.replace(lm, embedding_backend="hkv", tied_head=False)
    from repro.models.lm import CompositeLM

    model = CompositeLM(lm)
    mesh = make_dev_mesh(args.data_mesh, args.model_mesh)
    opt = {"adamw": adamw, "adamw8bit": adamw8bit, "adafactor": adafactor,
           "sgdm": sgdm}[args.optimizer]()

    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)

    stream = TokenStream(seed=args.seed, batch=args.batch, seq=args.seq,
                         vocab=lm.vocab, alpha=1.0)

    if args.backend == "hkv":
        table = ShardedHKVTable.create(
            mesh,
            HKVEmbedding(
                capacity=max(256, (2 * lm.vocab // 128) * 128),
                dim=lm.d_model,
                optimizer=SparseOptimizer("rowwise_adagrad", lr=0.05),
                # two-tier hierarchy per shard (DESIGN.md §2.5); the cold
                # plane stays in device memory under shard_map
                hot_capacity=args.hkv_hot_capacity,
                cold_value_tier="hbm",
            ),
        )
        builder = StepBuilder(model, opt)

        @jax.jit
        def step_fn(state, batch):
            params, opt_state, table = state
            params, opt_state, table, metrics = builder.train_step_hkv(
                params, opt_state, table, batch
            )
            return (params, opt_state, table), metrics

        state = (params, opt_state, table)
    else:
        builder = StepBuilder(model, opt)

        @jax.jit
        def step_fn(state, batch):
            params, opt_state = state
            params, opt_state, metrics = builder.train_step(params, opt_state, batch)
            return (params, opt_state), metrics

        state = (params, opt_state)

    def batch_fn(step):
        toks, labels = stream.batch_at(step)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

    driver = TrainDriver(
        step_fn=step_fn,
        batch_fn=batch_fn,
        state=state,
        ckpt_dir=args.ckpt_dir,
        cursor=DataCursor(seed=args.seed, step=0),
        checkpoint_every=args.checkpoint_every,
    )
    hist = driver.run(args.steps)
    losses = hist["loss"]
    print(f"[train] {args.arch} backend={args.backend}: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps "
          f"({hist['restarts']} restarts)")
    return hist


if __name__ == "__main__":
    main()
