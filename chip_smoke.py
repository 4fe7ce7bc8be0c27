#!/usr/bin/env python3
"""Quickest proof that the system runs on a TPU, through its public calls.

  python chip_smoke.py             # one chip: backtest, tune, dispatch, serve
  python chip_smoke.py --chips 4   # the sharded coupled tuner on 4 chips

Every phase runs at a size users run (a year of hourly settlement; a
model config at its published widths, random weights from ``--seed``),
checks its result against the repository's own reference path, and
prints one JSON line: shapes, cold (compile included) and warm wall
seconds around work that ends in ``block_until_ready``, the backend
compile seconds spent in the phase (``compile_s``), the process's
peak device bytes so far, the agreement numbers and their bounds, and
whether the program that ran holds a Mosaic kernel (``tpu_custom_call``).
The last line is ``{"ok": true, "device": {...}}`` only if every phase
passed. Without an accelerator the script exits non-zero and prints no
result; a phase that raises fails the run.

Inputs come from the calibrated market presets and seeds; nothing is
downloaded. Everything runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

REGIONS = ("germany", "france", "south_australia")   # calibrated presets
HOURS = 8760
DISPATCH_MIN_DWELL = 2


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _compiled(jitted, *args, **kwargs) -> tuple[bool, int]:
    """Whether the compiled program of ``jitted(*args, **kwargs)`` holds
    a Mosaic kernel, and its temporary bytes (which ``peak_bytes_in_use``
    does not count). JAX reuses the executable the call compiled."""
    c = jitted.lower(*args, **kwargs).compile()
    return ("tpu_custom_call" in c.as_text(),
            c.memory_analysis().temp_size_in_bytes)


def _auto_is(jitted, explicit) -> tuple[bool, float]:
    """Whether the automatic call just made compiled the same program as
    ``explicit()``, which pins the kernel path: running it then adds no
    entry to ``jitted``'s cache. Returns that and the explicit call's
    (warm) wall seconds."""
    n = jitted._cache_size()
    _, secs = _timed(explicit)
    return n > 0 and jitted._cache_size() == n, secs


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def markets(n: int, hours: int):
    from repro.energy.presets import region_params
    return [region_params(REGIONS[i % len(REGIONS)], seed=i)
            .replace(n_hours=hours) for i in range(n)]


def systems(n: int, hours: int, p_avg: float):
    """Psi from 0.5 to 4 (the paper's range) x a spread of MW ratings."""
    from repro.core.tco import make_system
    psis = np.geomspace(0.5, 4.0, max(n // 4, 1))
    ratings = (0.5, 1.0, 2.0, 4.0)[:max(min(n, 4), 1)]
    return [make_system(float(psi) * hours * mw * p_avg, mw, float(hours))
            for psi in psis for mw in ratings][:n]


def policies(n: int):
    """Shutdown shares x in [0, 0.3] crossed with plain / hysteresis /
    partial-shutdown / idle-draw variants."""
    from repro.fleet import PolicySpec
    kinds = ({}, {"hysteresis": 0.9}, {"off_level": 0.3},
             {"idle_frac": 0.05, "restart_energy_mwh": 0.3,
              "restart_time_h": 0.25})
    xs = np.linspace(0.0, 0.3, max(n // len(kinds), 1))
    return [PolicySpec(f"x{x:.3f}_{k}", x=float(x), **kind)
            for x in xs for k, kind in enumerate(kinds)][:n]


def grid_of(n_markets: int, n_systems: int, n_policies: int, hours: int):
    from repro.fleet import build_grid
    mk = markets(n_markets, hours)
    return build_grid(mk, systems(n_systems, hours, mk[0].p_avg),
                      policies(n_policies))


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_backtest(n_markets=32, n_systems=32, n_policies=32,
                   hours=HOURS) -> dict:
    """`backtest` on markets x systems x policies rows; the Pallas scan
    against the vectorized XLA recurrence (`use_pallas=False`)."""
    from repro.fleet import backtest
    from repro.fleet.engine import _backtest_jit

    grid, setup_s = _timed(lambda: grid_of(n_markets, n_systems,
                                           n_policies, hours))
    rep, cold = _timed(lambda: backtest(grid))
    kernel, warm = _auto_is(_backtest_jit,
                            lambda: backtest(grid, use_pallas=True))
    ref, ref_cold = _timed(lambda: backtest(grid, use_pallas=False))
    ref, ref_warm = _timed(lambda: backtest(grid, use_pallas=False))
    mosaic, temp = _compiled(
        _backtest_jit, grid.prices, grid.market_idx, grid.system_idx,
        grid.policy_idx, grid.fixed, grid.power, grid.period, grid.p_on,
        grid.p_off, grid.off_level, grid.idle_frac,
        grid.restart_energy_mwh, grid.restart_time_h, use_pallas=True,
        block_b=128, block_t=512)
    starts_equal = bool(np.array_equal(np.asarray(rep.n_starts),
                                       np.asarray(ref.n_starts)))
    rel = {f: _rel(getattr(rep, f), getattr(ref, f))
           for f in ("cpc", "tco", "up_hours", "energy_cost")}
    # the kernel sums each 512-hour block, then the blocks; the reference
    # adds hour by hour. Over 8760 f32 terms that reorders rounding by
    # ~sqrt(T) ulp typically, T ulp (5e-4) at worst; one hour of wrong
    # on/off state moves a row by ~1/T (1.1e-4) *and* its start count,
    # which must match exactly
    bound = 1e-4
    return {
        "phase": "backtest", "rows": grid.n_rows, "hours": grid.n_hours,
        "grid_setup_s": setup_s, "cold_s": cold, "warm_s": warm,
        "xla_cold_s": ref_cold, "xla_warm_s": ref_warm,
        "auto_is_pallas": kernel, "tpu_custom_call": mosaic,
        "n_starts_equal": starts_equal, "max_rel_diff": rel,
        "bound": bound, "peak_bytes": _peak_bytes(),
        "program_temp_bytes": temp,
        "ok": kernel and mosaic and starts_equal
        and max(rel.values()) <= bound,
    }


def _first_step_loss(raw, problem, tau, block_t, use_pallas):
    """The tuner's uncoupled per-row CPC ratio (`soft_costs`) with the
    soft-scan path pinned, summed as the hot loop sums it."""
    import jax.numpy as jnp
    from repro.fleet.engine import fleet_costs
    from repro.kernels.soft_scan import soft_scan_parts
    from repro.tune import transform
    phys = transform(raw)
    p = problem.row_prices()
    scan, _, _ = soft_scan_parts(p, phys.p_on, phys.p_off, phys.off_level,
                                 problem.idle_frac, tau=tau, fused=True,
                                 block_t=block_t, use_pallas=use_pallas)
    c = fleet_costs(scan, price_sum=problem.price_sum, fixed=problem.fixed,
                    power=problem.power, period=problem.period,
                    restart_energy_mwh=problem.restart_energy_mwh,
                    restart_time_h=problem.restart_time_h,
                    n_samples=p.shape[1])
    return jnp.sum(c.cpc / c.cpc_ao)


def phase_tune(n_markets=16, n_systems=16, n_policies=16, hours=HOURS,
               steps=30) -> dict:
    """`optimize` through the fused soft-scan VJP; the first step's
    gradient from the Pallas pair against the XLA pair."""
    import jax
    from repro.tune import (TuneConfig, init_from_grid, optimize,
                            problem_from_grid, tune_loop)

    grid = grid_of(n_markets, n_systems, n_policies, hours)
    cfg = TuneConfig(steps=steps)
    res, cold = _timed(lambda: optimize(grid, cfg))
    res, warm = _timed(lambda: optimize(grid, cfg))

    # the hot loop optimize ran (one program on one chip) is the one
    # called here, and it holds the kernel pair the soft scan selects on
    # this backend. The loop donates its parameter carry, so each call
    # gets its own copy.
    problem = problem_from_grid(grid)
    loop_is_auto, _ = _auto_is(tune_loop, lambda: tune_loop(
        init_from_grid(grid), problem, None, cfg=cfg, telemetry=False))
    loop_mosaic, temp = _compiled(tune_loop, init_from_grid(grid), problem,
                                  None, cfg=cfg, telemetry=False)
    raw = init_from_grid(grid)
    grads = {up: jax.jit(jax.grad(functools.partial(
        _first_step_loss, problem=problem, tau=cfg.tau_start,
        block_t=cfg.block_t, use_pallas=up))) for up in (True, False)}
    g_p = grads[True](raw)
    g_x = grads[False](raw)
    mosaic = _compiled(grads[True], raw)[0]
    rel = {name: _rel(getattr(g_p, name), getattr(g_x, name))
           for name in g_x._fields}
    finite = bool(np.all(np.isfinite(np.asarray(res.cpc))))
    # both pairs run the same per-hour math; the kernel scans each block
    # by log-depth doubling, the XLA pair hour by hour, so gradients
    # differ by f32 reassociation through 8760 hours
    bound = 1e-3
    return {
        "phase": "tune", "rows": grid.n_rows, "hours": grid.n_hours,
        "steps": steps, "cold_s": cold, "warm_s": warm,
        "loop_is_optimize_program": loop_is_auto,
        "loop_tpu_custom_call": loop_mosaic,
        "grad_tpu_custom_call": mosaic,
        "first_step_grad_max_rel_diff": rel, "bound": bound,
        "cpc_mean": float(np.mean(res.cpc)), "cpc_finite": finite,
        "improvement_vs_best_mean": float(np.mean(res.improvement_vs_best)),
        "peak_bytes": _peak_bytes(), "program_temp_bytes": temp,
        "ok": loop_is_auto and loop_mosaic and mosaic and finite
        and max(rel.values()) <= bound,
    }


def dispatch_problem(n_sites=64, hours=HOURS):
    """``n_sites`` sites over ``n_sites // 2`` market draws, a full and a
    partial shutdown policy per market, 1 MW each; demand is 35 % of the
    ratings."""
    from repro.dispatch import DispatchConfig, build_problem
    from repro.fleet import PolicySpec, build_grid
    mk = markets(n_sites // 2, hours)
    grid = build_grid(mk, systems(1, hours, mk[0].p_avg),
                      [PolicySpec("x10", x=0.10),
                       PolicySpec("x10_part", x=0.10, off_level=0.3)])
    prices = np.asarray(grid.prices)[np.asarray(grid.market_idx)]
    cfg = DispatchConfig(demand_frac=0.35, migrate_cost=4.0,
                         min_dwell_h=DISPATCH_MIN_DWELL)
    return build_problem(prices, np.asarray(grid.p_on),
                         np.asarray(grid.p_off), np.asarray(grid.off_level),
                         np.ones(prices.shape[0], np.float32), cfg)


def phase_dispatch(n_sites=64, hours=HOURS, tau=2.0) -> dict:
    """`dispatch` against `kernels.ref.dispatch_ref`, and the fused soft
    dispatch forward and backward against its XLA form."""
    import jax
    import jax.numpy as jnp
    from repro.dispatch import dispatch, segment_keys
    from repro.kernels.dispatch_scan import (_dispatch_scan_padded,
                                             dispatch_scan)
    from repro.kernels.ref import dispatch_ref
    from repro.kernels.soft_dispatch import soft_dispatch

    prob = dispatch_problem(n_sites, hours)
    args = (prob.avail_mw, prob.order, prob.rank, prob.demand_mw)
    res, cold = _timed(lambda: dispatch(prob))
    kernel, _ = _auto_is(_dispatch_scan_padded, lambda: dispatch_scan(
        *args, min_dwell=prob.min_dwell_h, interpret=False))
    res, warm = _timed(lambda: dispatch(prob))
    ref_fn = jax.jit(functools.partial(dispatch_ref,
                                       min_dwell=prob.min_dwell_h))
    ref, ref_s = _timed(lambda: ref_fn(*args))
    ref = np.asarray(ref)
    mosaic = _compiled(jax.jit(functools.partial(
        dispatch_scan, min_dwell=prob.min_dwell_h, interpret=False)),
        *args)[0]
    hard_diff = float(np.max(np.abs(res.alloc_mw - ref)))

    keys = jnp.asarray(segment_keys(prob.prices, prob.migrate_cost),
                       jnp.float32)
    order = jnp.asarray(prob.order)
    avail = jnp.asarray(prob.avail_mw)
    demand = jnp.asarray(prob.demand_mw, jnp.float32)
    weight = jnp.asarray(prob.prices) / float(np.mean(prob.prices))

    def cost(a, d, use_pallas):
        alloc = soft_dispatch(a, keys, order, d, tau=tau,
                              min_dwell=prob.min_dwell_h, fused=True,
                              use_pallas=use_pallas)
        return jnp.sum(alloc * weight), alloc

    soft = {}
    for up in (True, False):
        fn = jax.jit(jax.value_and_grad(
            functools.partial(cost, use_pallas=up), argnums=(0, 1),
            has_aux=True))
        out, cold_s = _timed(lambda: fn(avail, demand))
        out, warm_s = _timed(lambda: fn(avail, demand))
        soft[up] = (out, cold_s, warm_s, fn)
    soft_mosaic = _compiled(soft[True][3], avail, demand)[0]
    (_, alloc_p), (ga_p, gd_p) = soft[True][0]
    (_, alloc_x), (ga_x, gd_x) = soft[False][0]
    soft_fwd = float(np.max(np.abs(np.asarray(alloc_p)
                                   - np.asarray(alloc_x))))
    soft_bwd = max(_rel(ga_p, ga_x), _rel(gd_p, gd_x))
    demand_mw = float(np.max(prob.demand_mw))
    # hard: Mosaic and XLA evaluate the same shared per-hour ops (exact
    # one-hot gathers, a fixed prefix-sum association, clips); the
    # difference must stay at f32 rounding of the demand, 1e-5 of it.
    # soft: sigmoids and 30 bisection steps per hour compiled by two
    # compilers; allocation to 1e-3 of demand, gradients to 1e-2
    # relative
    b_hard, b_soft_fwd, b_soft_bwd = 1e-5 * demand_mw, 1e-3 * demand_mw, \
        1e-2
    return {
        "phase": "dispatch", "sites": int(prob.prices.shape[0]),
        "hours": int(prob.prices.shape[1]), "demand_mw": demand_mw,
        "cold_s": cold, "warm_s": warm, "ref_xla_s": ref_s,
        "auto_is_mosaic": kernel, "tpu_custom_call": mosaic,
        "hard_max_abs_diff_mw": hard_diff,
        "hard_bit_identical": hard_diff == 0.0, "hard_bound_mw": b_hard,
        "soft_tpu_custom_call": soft_mosaic,
        "soft_cold_s": soft[True][1], "soft_warm_s": soft[True][2],
        "soft_xla_cold_s": soft[False][1], "soft_xla_warm_s": soft[False][2],
        "soft_fwd_max_abs_diff_mw": soft_fwd, "soft_fwd_bound_mw": b_soft_fwd,
        "soft_grad_max_rel_diff": soft_bwd, "soft_grad_bound": b_soft_bwd,
        "peak_bytes": _peak_bytes(),
        "ok": kernel and mosaic and soft_mosaic
        and hard_diff <= b_hard and soft_fwd <= b_soft_fwd
        and soft_bwd <= b_soft_bwd,
    }


def phase_serve(arch="qwen1.5-0.5b", full_config=True, slots=8,
                max_seq=1024, n_requests=16, prompt_len=128, max_new=32,
                seed=0) -> dict:
    """`launch.serve`'s engine at the registered widths answers the
    requests twice (cold, then warm); prefill logits against a float32
    forward of the same weights."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import build_engine, serve_config, submit_random
    from repro.models.model import prefill
    from repro.serving.engine import ServeConfig

    cfg = serve_config(arch, full_config=full_config)
    eng = build_engine(cfg, ServeConfig(slots=slots, max_seq=max_seq),
                       seed=seed, price_gate=False)

    waves = -(-n_requests // slots)

    def answer(round_seed):
        done = len(eng.completed)
        submit_random(eng, n_requests, prompt_len=prompt_len,
                      max_new=max_new, seed=round_seed)
        tokens0 = eng.tokens_served
        for _ in range(2 * waves * max_new):
            if len(eng.completed) == done + n_requests:
                return eng.tokens_served - tokens0
            eng.tick()
        raise RuntimeError("serving engine left requests unanswered")

    toks, cold = _timed(lambda: answer(seed))
    toks, warm = _timed(lambda: answer(seed + 1))
    outputs = [len(r.output) for r in eng.completed]

    prompt = np.random.default_rng(seed).integers(
        2, cfg.vocab - 1, size=(1, prompt_len)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt)}
    logits = jax.jit(functools.partial(prefill, cfg=cfg, max_seq=max_seq))(
        eng.params, batch)[0]
    cfg32 = cfg.replace(dtype="float32", param_dtype="float32",
                        kv_cache_dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32)
                            if jnp.issubdtype(x.dtype, jnp.floating) else x,
                            eng.params)
    with jax.default_matmul_precision("highest"):
        logits32 = jax.jit(functools.partial(
            prefill, cfg=cfg32, max_seq=max_seq))(params32, batch)[0]
    rel = _rel(logits, logits32)
    finite = bool(np.all(np.isfinite(np.asarray(logits))))
    # bf16 activations and weights through every layer against f32
    bound = 5e-2
    return {
        "phase": "serve", "arch": cfg.name, "layers": cfg.n_layers,
        "d_model": cfg.d_model, "vocab": cfg.vocab, "slots": slots,
        "max_seq": max_seq, "requests": n_requests,
        "prompt_len": prompt_len, "max_new": max_new,
        "cold_s": cold, "warm_s": warm, "warm_tokens": int(toks),
        "warm_tokens_per_s": toks / warm,
        "all_answered": all(n == max_new for n in outputs),
        "prefill_logits_max_rel_diff": rel, "bound": bound,
        "logits_finite": finite, "peak_bytes": _peak_bytes(),
        "ok": finite and rel <= bound and all(n == max_new
                                              for n in outputs),
    }


# ---------------------------------------------------------------------------
# four chips: the sharded coupled tuner
# ---------------------------------------------------------------------------

def phase_sharded_tune(n_chips=4, n_markets=8, n_systems=8, n_policies=16,
                       hours=HOURS, tau=5.0) -> dict:
    """The coupled objective under `ExecutionPlan(mode='sharded')`'s
    shard_map over `parallel.row_mesh` (fleet aggregates psum-reduced)
    against the single program, to the 4-ULP bound of the
    ``coupled_shard_ulp_ok`` gate; rows must land on every chip."""
    import jax
    from repro.dispatch import DispatchConfig
    from repro.execution import Coupling, ExecutionPlan
    from repro.tune import (TuneConfig, dispatch_coupling_from_grid,
                            init_from_grid, optimize, problem_from_grid,
                            sharded_soft_objective, soft_objective)

    dcfg = DispatchConfig(demand_frac=0.25, migrate_cost=4.0,
                          min_dwell_h=DISPATCH_MIN_DWELL)
    grid = grid_of(n_markets, n_systems, n_policies, hours)
    problem, raw = problem_from_grid(grid), init_from_grid(grid)
    coupling = dispatch_coupling_from_grid(grid, dcfg)
    kw = dict(dispatch_blend=0.5, dispatch_min_dwell=dcfg.min_dwell_h,
              penalty_weight=10.0, power_cap_mw=0.6 * float(
                  np.sum(np.asarray(grid.power)
                         * np.asarray(problem.site_weight))))

    def single(r):
        return soft_objective(r, problem, tau, dispatch=coupling,
                              reduction="sum", **kw)[0]

    def sharded(r):
        return sharded_soft_objective(r, problem, tau, n_dev=n_chips,
                                      coupling=coupling, **kw)

    loss_1, single_s = _timed(lambda: jax.jit(single)(raw))
    loss_n, sharded_s = _timed(lambda: jax.jit(sharded)(raw))
    f1, fn = float(loss_1), float(loss_n)
    err_ulp = abs(fn - f1) / float(np.spacing(np.abs(np.float32(f1))))
    g_1 = jax.jit(jax.grad(single))(raw)
    g_n = jax.jit(jax.grad(sharded))(raw)
    leaf = g_n.raw_off
    shard_devices = sorted({s.device.id for s in leaf.addressable_shards})
    shard_rows = sorted({s.data.shape[0] for s in leaf.addressable_shards})
    placed = (len(leaf.sharding.device_set) == n_chips
              and len(shard_devices) == n_chips
              and shard_rows == [grid.n_rows // n_chips])
    grad_rel = max(_rel(getattr(g_n, f), getattr(g_1, f))
                   for f in g_1._fields)
    # the shards differ from the single program in the order the psum
    # adds the fleet aggregates and in the rows per program; on 4 virtual
    # CPU devices (32 rows x 96 h) the gradients differ by 1.8e-5. A
    # cotangent summed over the shards, the fault `psum_id` guards
    # against, leaves the loss within 1 ULP and moves the gradient by
    # n_chips - 1 (3.0 there)
    grad_bound = 1e-3

    cfg = TuneConfig(steps=8, plan=ExecutionPlan(mode="sharded"),
                     coupling=Coupling(dispatch=dcfg))
    res, tune_s = _timed(lambda: optimize(grid, cfg))
    finite = bool(np.all(np.isfinite(np.asarray(res.cpc))))
    return {
        "phase": "sharded_tune", "chips": n_chips, "rows": grid.n_rows,
        "hours": grid.n_hours, "sites": int(coupling.prices.shape[0]),
        "loss_single": f1, "loss_sharded": fn, "err_ulp": err_ulp,
        "ulp_bound": 4.0, "single_cold_s": single_s,
        "sharded_cold_s": sharded_s, "grad_max_rel_diff": grad_rel,
        "grad_bound": grad_bound,
        "shard_devices": shard_devices, "rows_per_shard": shard_rows,
        "rows_on_every_chip": placed, "sharded_optimize_s": tune_s,
        "sharded_optimize_cpc_finite": finite,
        "peak_bytes": _peak_bytes(),
        "ok": err_ulp <= 4.0 and grad_rel <= grad_bound and placed
        and finite,
    }


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded coupled tuner and the "
                    "single program it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the serving phase's weights and prompts")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import use_compile_cache

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(json.dumps({"compile_cache": use_compile_cache(),
                      "device_kind": dev.device_kind,
                      "devices": len(devices)}), flush=True)

    if args.chips == 4:
        phases = [functools.partial(phase_sharded_tune, n_chips=4)]
    else:
        phases = [phase_backtest, phase_tune, phase_dispatch,
                  functools.partial(phase_serve, seed=args.seed)]
    compile_s = 0.0

    def count_compile(event, secs, **_):
        # backend compiles; a persistent-cache hit counts its load time
        nonlocal compile_s
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s += secs

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    ok = True
    for phase in phases:
        before = compile_s
        report = phase()
        report["compile_s"] = compile_s - before
        print(json.dumps(report), flush=True)
        ok = ok and bool(report["ok"])
    if not ok:
        print("chip_smoke: a phase failed its check", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
