"""Production training launcher.

Single-host usage (smoke / development):
    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 100 --ckpt /tmp/ck

Cluster usage (per-host, under your pod scheduler):
    python -m repro.launch.train --arch dbrx-132b \
        --coordinator $COORD --num-hosts 64 --host-id $ID --ckpt gs://...

The launcher wires together the pieces the rest of the framework provides:
  * jax.distributed initialization (multi-host),
  * the production mesh + FSDP/TP shardings (repro.sharding),
  * sharded-jit train step with remat + grad accumulation (repro.train),
  * checkpoint/restart + preemption guard + straggler monitor (repro.runtime),
  * elastic re-mesh on degraded restarts (repro.runtime.fault.plan_remesh).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--data", type=int, default=0, help="data-parallel degree")
    ap.add_argument("--model", type=int, default=1, help="tensor-parallel degree")
    args = ap.parse_args()

    if args.coordinator:
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_hosts,
                                   process_id=args.host_id)

    from repro.configs import get_config
    from repro.models.model import ModelFlags, build_model
    from repro.runtime.fault import plan_remesh
    from repro.train import TrainLoop

    run = get_config(args.arch)
    if args.smoke:
        run = run.smoke()

    n_dev = len(jax.devices())
    mesh_shape = plan_remesh(n_dev, args.model)
    if mesh_shape is None:
        raise SystemExit(f"cannot build a mesh from {n_dev} devices at "
                         f"TP={args.model}")
    print(f"[launch] devices={n_dev} mesh={mesh_shape}")

    flags = ModelFlags(remat="full" if not args.smoke else "none",
                       act_batch_axes="data" if n_dev > 1 else None,
                       act_batch_extent=mesh_shape[0])
    model = build_model(run, flags)
    params = model.init(jax.random.PRNGKey(run.train.seed),
                        dtype=run.model.param_dtype)

    loop = TrainLoop(model, run, params, ckpt_dir=args.ckpt,
                     host_id=args.host_id)
    loop.guard.install()
    if loop.try_restore():
        print(f"[launch] restored step {loop.step}")
    steps = args.steps if args.steps is not None else run.train.steps
    while loop.step < steps and not loop.guard.should_save():
        stats = loop.run_steps(min(10, steps - loop.step))
        print(f"[train] step={loop.step} loss={stats['loss']:.4f} "
              f"lr={stats['lr']:.2e} {stats['step_time']*1e3:.0f}ms "
              f"stragglers={loop.monitor.stragglers()}")
    if args.ckpt:
        loop.save()
        loop.ckpt.wait()


if __name__ == "__main__":
    main()
