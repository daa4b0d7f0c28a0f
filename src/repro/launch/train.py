"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube3-4b \
      --smoke --steps 50 --batch 8 --seq 256 [--workdir ckpts] \
      [--ckpt-every 20] [--fail-at 30]  [--mesh d,m]

--smoke uses the reduced config (CPU-runnable); the full configs are for
real pods.  --fail-at injects a fault to drill the restore path.
"""
from __future__ import annotations

import argparse

from repro.configs.base import TrainConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.distributed.fault import FaultInjector
from repro.launch.mesh import make_mesh
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="h2o-danube3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--sharding", default="tp",
                    choices=["tp", "fsdp", "fsdp_pod"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--mesh", default=None,
                    help="data,model (requires enough devices)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 10, 1),
                       microbatch=args.microbatch,
                       sharding_mode=args.sharding,
                       grad_compression=args.grad_compression)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
    injector = FaultInjector((args.fail_at,)) if args.fail_at else None
    report = train(cfg, tcfg, steps=args.steps,
                   batch_shape=(args.batch, args.seq), mesh=mesh,
                   workdir=args.workdir, ckpt_every=args.ckpt_every,
                   injector=injector)
    print(f"\nfinal loss {report.final_loss:.4f} over {report.steps_run} "
          f"steps; restarts={report.restarts}; "
          f"median step {report.median_step_s*1e3:.0f} ms; "
          f"stragglers={len(report.straggler_events)}")


if __name__ == "__main__":
    main()
