"""Training driver: ``python -m repro.launch.train --arch <id> [--smoke] ...``

On CPU this runs the reduced (smoke) configs end-to-end — synthetic Markov data,
AdamW, checkpointing — and is used by examples/train_target_drafter.py to
produce the aligned (target, drafter) pairs for the acceptance-rate study.
On a real slice the same code drives the full configs over the production mesh.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt
from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.data import pipeline
from repro.launch.mesh import mesh_axis_sizes
from repro.launch import cli_args, steps
from repro.models.model import build_model
from repro.models.specs import ShardingPolicy
from repro.obs import clock
from repro.training import optimizer as opt


def train(cfg, *, steps_n=200, batch=8, seq=64, lr=1e-3, seed=0, ckpt_path=None,
          mesh=None, log_every=20, data_seed=0, data_order=2):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    ocfg = opt.AdamWConfig(lr=lr, warmup_steps=max(10, steps_n // 20),
                           total_steps=steps_n)
    opt_state = opt.init(params)

    dcfg = pipeline.DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                               global_batch=batch, seed=data_seed,
                               order=data_order)
    stream = pipeline.batches(dcfg)

    from repro.training.train_loop import make_train_step
    step_fn = jax.jit(make_train_step(model, ocfg))

    extras = {k: jnp.full(s.shape, 0.1, s.dtype)
              for k, s in model.extra_inputs(batch).items()}
    t0 = clock.wall()
    losses = []
    for i in range(steps_n):
        tokens, labels = pipeline.split_batch(next(stream))
        b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels), **extras}
        params, opt_state, metrics = step_fn(params, opt_state, b)
        losses.append(float(metrics["loss"]))
        if i % log_every == 0 or i == steps_n - 1:
            print(f"step {i:5d} loss {losses[-1]:.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"({(clock.wall()-t0)/(i+1):.2f}s/step)", flush=True)
    if ckpt_path:
        ckpt.save(ckpt_path, params, step=steps_n)
        print(f"saved {ckpt_path}")
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--drafter", action="store_true", help="train the drafter config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    mod = registry.get(args.arch)
    if args.smoke:
        cfg = mod.smoke_config()
        if args.drafter:
            cfg = cfg.replace(num_layers=max(1, cfg.num_layers - 1),
                              d_model=max(64, cfg.d_model // 2),
                              num_heads=max(1, cfg.num_heads // 2),
                              num_kv_heads=max(1, cfg.num_kv_heads // 2),
                              d_ff=max(64, cfg.d_ff // 2),
                              name=cfg.name + "-draft")
    else:
        cfg = mod.drafter_config() if args.drafter else mod.config()
    print(f"training {cfg.name} ({cfg.family}) params~{cfg.param_count():,}")
    train(cfg, steps_n=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
          ckpt_path=args.ckpt)


if __name__ == "__main__":
    cli_args.enable_compile_cache()
    main()
