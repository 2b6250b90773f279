"""End-to-end training driver.

Runs a real training loop on the local devices (CPU here, TPU in prod):

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b-smoke \
        --steps 100 --batch 8 --seq 128 --ckpt /tmp/ckpt

For the ~100M-class end-to-end example see examples/train_100m.py (which
calls into this module with a scaled config).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.data import batch_for_model
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model, ShardCtx
from repro.training import (OptimizerConfig, TrainConfig, init_optimizer,
                            make_train_step, save_checkpoint,
                            restore_checkpoint, latest_checkpoint)


def train(arch: str, steps: int, batch: int, seq: int, *, lr: float = 3e-4,
          microbatches: int = 1, failout: float = 0.0, ckpt_dir: str = "",
          ckpt_every: int = 200, log_every: int = 10, seed: int = 0,
          config_override=None):
    cfg = config_override or get_config(arch)
    model = Model(cfg, ShardCtx(None), remat=False)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    opt_state = init_optimizer(params)
    start = 0
    if ckpt_dir:
        last = latest_checkpoint(ckpt_dir)
        if last:
            state = restore_checkpoint(last, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start = int(opt_state["step"])
            print(f"restored step {start} from {last}")

    ocfg = OptimizerConfig(lr=lr, warmup_steps=max(10, steps // 20),
                           total_steps=steps)
    tcfg = TrainConfig(microbatches=microbatches, failout_prob=failout)
    step_fn = jax.jit(make_train_step(model, ocfg, tcfg))
    shape = InputShape("cli", seq, batch, "train")

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M steps={steps} "
          f"batch={batch} seq={seq}")
    t0 = time.time()
    metrics = {}
    for step in range(start, steps):
        b = batch_for_model(cfg, shape, step)
        params, opt_state, metrics = step_fn(
            params, opt_state, b, jax.random.fold_in(rng, step))
        if step % log_every == 0 or step == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            dt = time.time() - t0
            tput = (step - start + 1) * batch * seq / max(dt, 1e-9)
            print(f"step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                  f"gnorm {m['grad_norm']:.2f} lr {m['lr']:.2e} "
                  f"tok/s {tput:,.0f}", flush=True)
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            save_checkpoint(ckpt_dir, {"params": params, "opt": opt_state},
                            step + 1, jax.process_index() == 0)
    if ckpt_dir:
        save_checkpoint(ckpt_dir, {"params": params, "opt": opt_state},
                        steps, jax.process_index() == 0)
    return params, {k: float(v) for k, v in metrics.items()}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--failout", type=float, default=0.0)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    train(args.arch, args.steps, args.batch, args.seq, lr=args.lr,
          microbatches=args.microbatches, failout=args.failout,
          ckpt_dir=args.ckpt)


if __name__ == "__main__":
    main()
