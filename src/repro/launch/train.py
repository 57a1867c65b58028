"""End-to-end training driver.

Wires together: config/arch registry, placement plan (tier offload),
synthetic data pipeline with prefetch, AdamW with fp32 master, checkpoint
manager (async, retained), fault supervision (watchdog + retry +
straggler stats), and metrics logging.

CLI (runs on whatever devices exist; the production mesh path is exercised
by dryrun.py):

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import json
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.config.base import (ParallelConfig, RunConfig, ShapeConfig,
                               get_config)
from repro.checkpoint.manager import CheckpointManager
from repro.core.placement import plan_training_placement
from repro.data.synthetic import PrefetchLoader, synthetic_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, num_chips
from repro.models.model import Model
from repro.optim import adamw, schedule
from repro.runtime.fault import StepSupervisor, StragglerStats, StepTimeout
from repro.training.step import init_train_state, make_train_step


def train(cfg, shape: ShapeConfig, run: RunConfig,
          parallel: ParallelConfig = ParallelConfig(),
          mesh=None, log=print) -> dict:
    mesh = mesh or make_host_mesh()
    model = Model.create(cfg, mesh, parallel)
    plan = plan_training_placement(cfg, num_chips(mesh))
    log(f"[train] {cfg.name}: {model.num_params/1e6:.1f}M params, "
        f"placement={plan.kinds}")

    lr_fn = partial(schedule.warmup_cosine, peak_lr=run.learning_rate,
                    warmup_steps=run.warmup_steps, total_steps=run.steps)
    step_fn = jax.jit(
        make_train_step(model, adamw.AdamWConfig(
            weight_decay=run.weight_decay), lr_fn, offload_plan=plan),
        donate_argnums=(0, 1, 2))

    mgr = CheckpointManager(run.checkpoint_dir)
    def init():
        return init_train_state(model, jax.random.key(run.seed))
    (params_c, master, opt_state), start = mgr.restore_or_init(init)
    if start:
        log(f"[train] resumed from step {start}")

    loader = PrefetchLoader(cfg, shape, start_step=start, seed=run.seed)
    supervisor = StepSupervisor(min_timeout=300.0)
    stats = StragglerStats()
    history = []
    try:
        for step_idx, batch in loader:
            if step_idx >= run.steps:
                break
            t0 = time.perf_counter()
            try:
                (params_c, master, opt_state, metrics), dt = supervisor.run(
                    step_fn, params_c, master, opt_state, batch)
            except StepTimeout:
                log(f"[train] step {step_idx} timed out; restoring")
                (params_c, master, opt_state), _ = mgr.restore_or_init(init)
                continue
            if step_idx > start:        # skip compile-step outlier
                stats.record(dt)
            loss = float(metrics["loss"])
            history.append(loss)
            if step_idx % run.log_every == 0:
                log(f"[train] step={step_idx} loss={loss:.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} "
                    f"lr={float(metrics['lr']):.2e} dt={dt*1e3:.0f}ms")
            if run.checkpoint_every and step_idx and \
                    step_idx % run.checkpoint_every == 0:
                mgr.save(step_idx, (params_c, master, opt_state))
            if stats.inflated:
                log(f"[train] straggler warning: {stats.summary()}")
    finally:
        loader.close()
        mgr.wait()
    return {"history": history, "final_loss": history[-1] if history else None,
            "straggler": stats.summary()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("custom", args.seq, args.batch, "train")
    run = RunConfig(steps=args.steps, learning_rate=args.lr,
                    checkpoint_dir=args.ckpt_dir,
                    checkpoint_every=max(10, args.steps // 4))
    parallel = ParallelConfig(microbatches=args.microbatches)
    out = train(cfg, shape, run, parallel)
    print(json.dumps({"final_loss": out["final_loss"],
                      "straggler": out["straggler"]}))


if __name__ == "__main__":
    main()
