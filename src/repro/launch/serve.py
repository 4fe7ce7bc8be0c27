"""Serving launcher: price-aware batched inference.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --requests 32 --ticks 400 --region germany

Without ``--full-config`` the registered architecture is cut to a
reduced smoke size (`repro.configs.inputs.reduced_config`); with it the
engine serves the published widths, which needs an accelerator.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.base import ModelConfig, get_config
from repro.configs.inputs import reduced_config
from repro.energy.markets import generate_market
from repro.energy.presets import region_params
from repro.energy.stream import PriceStream
from repro.models.model import init_params
from repro.runtime.scheduler import EnergyAwareScheduler, SchedulerConfig
from repro.serving.engine import Request, ServeConfig, ServingEngine


def serve_config(arch: str, *, full_config: bool) -> ModelConfig:
    """The registered config, reduced unless ``full_config``."""
    cfg = get_config(arch)
    return cfg if full_config else reduced_config(cfg)


def build_engine(cfg: ModelConfig, scfg: ServeConfig, *, seed: int = 0,
                 region: str = "germany", psi: float = 2.0,
                 price_gate: bool = True) -> ServingEngine:
    """Weights initialised from ``seed``; admission gated by a seeded
    market of ``region`` unless ``price_gate`` is off."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    scheduler = None
    if price_gate:
        md = generate_market(region_params(region, seed=seed))
        scheduler = EnergyAwareScheduler(
            PriceStream(np.asarray(md.prices)),
            SchedulerConfig(psi=psi, mode="oracle"))
    return ServingEngine(params, cfg, scfg, scheduler=scheduler)


def submit_random(eng: ServingEngine, n: int, *, prompt_len: int,
                  max_new: int, seed: int = 0) -> None:
    """``n`` requests of uniform random prompt tokens."""
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(
                               2, eng.cfg.vocab - 1,
                               size=prompt_len).astype(np.int32),
                           max_new=max_new))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths (accelerator only)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--ticks", type=int, default=200)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--min-slots", type=int, default=0)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--region", default="germany")
    ap.add_argument("--psi", type=float, default=2.0)
    ap.add_argument("--no-price-gate", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = serve_config(args.arch, full_config=args.full_config)
    eng = build_engine(cfg, ServeConfig(slots=args.slots,
                                        min_slots=args.min_slots,
                                        max_seq=args.max_seq),
                       seed=args.seed, region=args.region, psi=args.psi,
                       price_gate=not args.no_price_gate)
    submit_random(eng, args.requests, prompt_len=8, max_new=16,
                  seed=args.seed)
    out = eng.run(ticks=args.ticks)
    print(json.dumps({k: (round(v, 6) if isinstance(v, float) else v)
                      for k, v in out.items()}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
