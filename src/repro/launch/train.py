"""Training launcher: energy-aware training of any assigned architecture.

On this CPU container it drives a *reduced* config end-to-end (real JAX
steps, simulated market clock); on a real cluster the same driver runs the
full config — the mesh comes from `make_production_mesh()` and the Trainer's
checkpoint/restore path is the shutdown/resume mechanism.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 200 --region germany --psi 2.0 --mode oracle
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import get_config
from repro.configs.inputs import reduced_config
from repro.energy.markets import generate_market
from repro.energy.presets import region_params
from repro.energy.stream import PriceStream
from repro.runtime.scheduler import EnergyAwareScheduler, SchedulerConfig
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--region", default="germany")
    ap.add_argument("--psi", type=float, default=2.0)
    ap.add_argument("--mode", default="oracle",
                    choices=["oracle", "rolling", "always-on"])
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture config (cluster only)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--fault-prob", type=float, default=0.0)
    ap.add_argument("--straggler-sigma", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced_config(cfg)

    md = generate_market(region_params(args.region, seed=args.seed))
    stream = PriceStream(np.asarray(md.prices))
    scheduler = None
    if args.mode != "always-on":
        scheduler = EnergyAwareScheduler(
            stream, SchedulerConfig(psi=args.psi, mode=args.mode))
        print("scheduler:", scheduler.stats_snapshot())

    trainer = Trainer(
        cfg,
        TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                      microbatches=args.microbatches,
                      grad_compress=args.grad_compress,
                      fault_prob_per_step=args.fault_prob,
                      straggler_sigma=args.straggler_sigma,
                      seed=args.seed),
        scheduler=scheduler, batch_size=args.batch, seq_len=args.seq)
    out = trainer.run()
    print(json.dumps({k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in out.items()}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
