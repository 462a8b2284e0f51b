"""Production training driver.

Wires together: config registry -> ComParX plan (tuned or baseline) ->
jitted train step -> deterministic resumable data -> async atomic
checkpoints -> heartbeat/failure handling.

Fault tolerance contract (1000+ node design):
* restart-from-latest is the default (``--resume auto``) — a requeued
  SLURM job continues exactly (data + RNG are step-indexed);
* checkpoints are atomic + keep-N, written async off the critical path;
* a missed heartbeat (straggling host) is surfaced via a watchdog so the
  scheduler can requeue; on this single-host container the watchdog just
  logs;
* elastic: ``--mesh`` may differ between runs — restore re-shards.

Usage:
  python -m repro.launch.train --arch granite-8b --smoke --steps 50
  python -m repro.launch.train --arch xlstm-125m --steps 200 --plan plan.json
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.configs import get_arch, get_shape
from repro.configs.base import ShapeConfig
from repro.core.plan import Plan, default_plan
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_test_mesh
from repro.runtime.backend import enable_compile_cache
from repro.train.step import init_train_state, jit_train_step


class Heartbeat:
    """Watchdog hook: on a pod, each host posts a heartbeat and the
    launcher requeues stragglers; standalone it records step latencies."""

    def __init__(self, warn_factor: float = 3.0):
        self.warn_factor = warn_factor
        self.history = []

    def beat(self, step: int, dt: float):
        self.history.append(dt)
        med = float(np.median(self.history[-20:]))
        if len(self.history) > 5 and dt > self.warn_factor * med:
            print(f"[heartbeat] step {step}: straggler suspected "
                  f"({dt:.2f}s vs median {med:.2f}s)")


def train(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + shape (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--plan", default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "never"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch")
    ap.add_argument("--seq", type=int, default=0, help="override seq len")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=100)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    shape = get_shape(args.shape)
    if args.smoke:
        cfg, shape = cfg.smoke(), shape.smoke()
    if args.batch or args.seq:
        shape = ShapeConfig(shape.name + "-cli",
                            args.seq or shape.seq_len,
                            args.batch or shape.global_batch, shape.kind)

    plan = Plan.load(args.plan) if args.plan else default_plan(cfg, shape)
    mesh = None if len(jax.devices()) == 1 else make_test_mesh(
        data=len(jax.devices()))
    print(f"[train] arch={cfg.name} shape={shape.name} "
          f"devices={len(jax.devices())}")
    print("[train] plan:\n" + plan.describe())

    step_fn, shardings = jit_train_step(cfg, mesh, plan,
                                        peak_lr=args.lr,
                                        warmup=args.warmup)
    params, opt = init_train_state(cfg, plan, jax.random.key(args.seed),
                                   shardings if mesh is not None else None)
    data = SyntheticLM(cfg, shape, seed=args.seed)
    store = CheckpointStore(
        args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                      f"repro_ckpt_{cfg.name}"), keep=3)
    start = 0
    if args.resume == "auto" and store.latest_step() is not None:
        start, state, extra = store.restore(
            {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        data.restore(extra["data"])
        print(f"[train] resumed from step {start}")

    hb = Heartbeat()
    losses = []
    for step in range(start, args.steps):
        batch = data.batch_at(step)
        data.state.step = step + 1
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch)
        jax.block_until_ready(metrics["total_loss"])
        dt = time.perf_counter() - t0
        hb.beat(step, dt)
        losses.append(float(metrics["total_loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step:5d} loss={losses[-1]:.4f} "
                  f"grad_norm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms")
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            store.save_async(step + 1, {"params": params, "opt": opt},
                             extra={"data": data.snapshot(),
                                    "plan": plan.to_json()})
    store.wait()
    if losses:
        print(f"[train] final loss {losses[-1]:.4f} "
              f"(start {losses[0]:.4f}); checkpoints: {store.steps()}")
    else:
        print(f"[train] nothing to do (resumed at step {start} "
              f">= {args.steps}); checkpoints: {store.steps()}")
    return losses


if __name__ == "__main__":
    enable_compile_cache()
    train()
