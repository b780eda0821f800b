"""Training driver: config-selected architecture, ROCKET input pipeline,
checkpoint/restart, straggler monitoring.

CPU-scale example (the e2e driver deliverable):
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
      --steps 200 --batch 8 --seq 64

On a real cluster the same driver runs under the production mesh with
``--mesh single|multi`` (the dry-run proves those configurations compile).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import ExecutionMode, LatencyModel, OffloadPolicy
from repro.core.latency import calibrate
from repro.data import InputPipeline, SyntheticLMSource
from repro.ft import Heartbeat, RestartManager, StragglerMonitor
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import adamw
from repro.sharding import api as shard_api
from repro.train import TrainConfig, init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mode", default="pipelined",
                    choices=["sync", "async", "pipelined"],
                    help="ROCKET tier-1 input movement mode")
    ap.add_argument("--movement", default="sync",
                    choices=["sync", "manual_dp", "manual_dp_bf16"],
                    help="tier-2 gradient movement (manual_dp needs an "
                         "active mesh with replicated params)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--calibrate", action="store_true",
                    help="recalibrate the latency model on this node")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)

    manual_axes = ()
    if args.movement.startswith("manual_dp"):
        # manual-DP over however many devices this host has
        mesh = make_mesh((len(jax.devices()),), ("data",))
        shard_api.set_mesh(mesh)
        manual_axes = ("data",)
    tcfg = TrainConfig(
        opt=adamw.AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                              total_steps=args.steps,
                              grad_sync_dtype="bfloat16"
                              if args.movement.endswith("bf16") else None),
        microbatches=args.microbatches,
        manual_dp_axes=manual_axes)
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))

    latency = None
    if args.calibrate:
        latency = calibrate(lambda b: jax.block_until_ready(jax.device_put(b)))
        print(f"calibrated latency model: L_fixed={latency.l_fixed_us:.1f}us "
              f"alpha={latency.alpha_us_per_mb:.2f}us/MB "
              f"(rel std {latency.rel_std:.1%})")

    policy = OffloadPolicy(mode=ExecutionMode(args.mode),
                           offload_threshold_bytes=1 << 12)
    source = SyntheticLMSource(cfg, shape, seed=0)
    pipeline = InputPipeline(source, policy, latency)

    ckpt_dir = args.ckpt_dir or os.path.join("checkpoints", cfg.name)
    cm = CheckpointManager(ckpt_dir)
    rm = RestartManager(cm, save_every=args.save_every)
    monitor = StragglerMonitor()
    hb = Heartbeat(os.path.join(ckpt_dir, "heartbeat.json"), host_id=0)

    params, opt_state = init_train_state(model, jax.random.key(0))
    start_step = 0
    latest = cm.latest_step()
    if latest is not None:
        (state, extra) = cm.restore(
            latest, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        if "data" in extra:
            pipeline.restore(extra["data"])
        start_step = latest
        print(f"resumed from step {latest}")

    t_train0 = time.perf_counter()
    for step in range(start_step, args.steps):
        t0 = time.perf_counter()
        batch = next(pipeline)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            toks = shape.tokens_per_step / dt
            print(f"step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"{dt*1e3:7.1f} ms/step {toks:9.0f} tok/s", flush=True)
        monitor.record_step(time.perf_counter() - t0, step)
        hb.beat(step)
        rm.maybe_save(step + 1, {"params": params, "opt": opt_state},
                      {"data": pipeline.state()})
    cm.wait()
    total = time.perf_counter() - t_train0
    print(f"done: {args.steps - start_step} steps in {total:.1f}s; "
          f"pipeline wait {pipeline.stats.wait_s:.2f}s "
          f"produce {pipeline.stats.produce_s:.2f}s; "
          f"engine {pipeline.engine.stats.snapshot()}")
    if monitor.events:
        print(f"straggler events: {monitor.events}")
    pipeline.close()


if __name__ == "__main__":
    main()
