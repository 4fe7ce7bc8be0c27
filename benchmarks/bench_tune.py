"""Policy-tuning throughput: row-steps/sec of the jitted fleet-wide
gradient loop, fused custom-VJP vs native autodiff, plus peak-memory.

One tuning step = forward + backward through the soft scan over all B
rows and T hours plus a vmapped Adam update — the figure of merit is
(rows x steps) / second, i.e. how many per-site gradient refinements
the tuner sustains. Both variants time the *same* compiled object the
tuner runs (`repro.tune.tune_loop`: annealing, Adam scan and hard
re-evaluation in one program), differing only in
``TuneConfig.fused`` — so the reported speedup is exactly what
switching the VJP buys. Warm timings are the median of ``repeats``
(`benchmarks.common.timed`), and the compiled programs' XLA
`memory_analysis` peak temp sizes quantify the HBM-resident
intermediates the checkpointed backward removes.
"""

from __future__ import annotations

import numpy as np

import jax

from benchmarks.common import timed, write_artifact
from repro.core.tco import make_system
from repro.dispatch import DispatchConfig
from repro.energy.presets import region_params
from repro.fleet import PolicySpec, build_grid
from repro.tune import (TuneConfig, init_from_grid, optimize,
                        problem_from_grid, tune_loop)


def _grid(n_markets: int, n_systems: int, hours: int):
    markets = [region_params("germany", seed=s).replace(n_hours=hours)
               for s in range(n_markets)]
    p_avg = markets[0].p_avg
    psis = np.geomspace(0.5, 4.0, n_systems)
    systems = [make_system(float(psi) * hours * 1.0 * p_avg, 1.0,
                           float(hours)) for psi in psis]
    policies = [
        PolicySpec("always_on"),
        PolicySpec("x1", x=0.01), PolicySpec("x3", x=0.03),
        PolicySpec("x8", x=0.08), PolicySpec("x15", x=0.15),
        PolicySpec("x25", x=0.25),
        PolicySpec("x3_hyst", x=0.03, hysteresis=0.9),
        PolicySpec("x8_hyst", x=0.08, hysteresis=0.85),
    ]
    return build_grid(markets, systems, policies)


def _time_variant(problem, raw0_np, cfg: TuneConfig, repeats: int, *,
                  telemetry: bool = False, label: str | None = None):
    """Median warm wall time of the full jitted loop + compiled peak
    temp bytes. Compiles exactly once (the timed calls run the lowered
    executable directly — also the object `memory_analysis` reads);
    ``tune_loop`` donates its parameter carry, so every call rebuilds
    the (tiny) raw-parameter arrays from host copies. ``telemetry``
    compiles the variant with the `repro.obs` side-outputs; ``label``
    records the compiled program's XLA cost/memory analysis into the
    active trace (`repro.obs.profiling.record_compiled`)."""
    raw0 = jax.tree.map(jax.numpy.asarray, raw0_np)
    compiled = tune_loop.lower(raw0, problem, cfg=cfg,
                               telemetry=telemetry).compile()
    if label is not None:
        from repro.obs.profiling import record_compiled
        record_compiled(label, compiled)
    mem = compiled.memory_analysis()
    temp_bytes = None if mem is None else int(mem.temp_size_in_bytes)

    def call():
        out = compiled(jax.tree.map(jax.numpy.asarray, raw0_np), problem)
        jax.block_until_ready(out[0])
        return out

    _, warm_us = timed(call, repeats=repeats, stat="median")
    return warm_us / 1e6, temp_bytes


def bench_tune(n_markets: int = 8, n_systems: int = 4,
               hours: int = 2190, steps: int = 200, repeats: int = 3,
               with_optimize: bool = True) -> dict:
    """8 x 4 x 8 = 256 rows x 2190 h, 200 annealed Adam steps,
    fused custom-VJP vs native-autodiff backward at matched configs."""
    grid = _grid(n_markets, n_systems, hours)
    problem = problem_from_grid(grid)
    raw0_np = jax.tree.map(np.asarray, init_from_grid(grid))
    row_steps = grid.n_rows * steps

    fused_s, fused_tmp = _time_variant(
        problem, raw0_np, TuneConfig(steps=steps), repeats,
        label="tune_loop.fused")
    native_s, native_tmp = _time_variant(
        problem, raw0_np, TuneConfig(steps=steps, fused=False), repeats,
        label="tune_loop.native")
    # telemetry A/B: the same fused program with the `repro.obs`
    # side-outputs compiled in, timed under a live (throwaway) trace
    # run — this measures the <10% wall-clock overhead the telemetry
    # subsystem promises, and `check_regression` gates the ratio
    import tempfile

    from repro import obs
    with tempfile.TemporaryDirectory() as td:
        with obs.capture(td, run_id="bench_tune_telemetry"):
            tel_s, _ = _time_variant(
                problem, raw0_np, TuneConfig(steps=steps), repeats,
                telemetry=True, label="tune_loop.telemetry")

    out = {
        "rows": grid.n_rows,
        "hours": hours,
        "steps": steps,
        "repeats": repeats,
        "wall_s_fused": fused_s,
        "wall_s_native": native_s,
        "row_steps_per_s_fused": row_steps / fused_s,
        "row_steps_per_s_native": row_steps / native_s,
        "speedup_fused_vs_native": native_s / fused_s,
        "wall_s_telemetry": tel_s,
        "telemetry_overhead_frac": tel_s / fused_s - 1.0,
        "telemetry_speed_ratio": fused_s / tel_s,
        "temp_bytes_fused": fused_tmp,
        "temp_bytes_native": native_tmp,
        "temp_reduction": (native_tmp / fused_tmp
                           if fused_tmp and native_tmp else None),
    }

    if with_optimize:
        # end-to-end quality numbers (fused path, the default) — the
        # hard guarantee and how often the gradient beats the sweep
        res = optimize(grid, TuneConfig(steps=steps))
        out.update({
            "improvement_vs_best_mean": float(
                res.improvement_vs_best.mean()),
            "improvement_vs_own_mean": float(
                res.improvement_vs_own.mean()),
            "rows_strictly_better": int(
                (res.cpc < res.cpc_swept_best * (1 - 1e-6)).sum()),
            "loss_first": float(res.history["loss"][0]),
            "loss_last": float(res.history["loss"][-1]),
        })
    write_artifact("bench_tune", out)
    return out


def fd_grad_worst_rel_err(t: int = 48) -> float:
    """Fixed-seed central-FD-vs-autodiff sweep over every raw
    coordinate of the dispatch-aware soft objective in f64, returning
    the worst relative error. The single source of the FD harness:
    `tests/test_soft_dispatch.py` asserts it under the 1e-3 acceptance
    tolerance and `benchmarks.check_regression` gates its reciprocal
    margin, so the test and the CI gate cannot drift apart on what
    "FD-correct" means."""
    import jax
    import jax.numpy as jnp

    from repro.energy.markets import MarketParams
    from repro.tune import (PolicyParams, dispatch_coupling_from_grid,
                            soft_objective)

    with jax.enable_x64(True):
        grid = build_grid([MarketParams(n_hours=t, seed=s)
                           for s in range(2)],
                          [make_system(0.5 * t * 80.0, 1.0, float(t))],
                          [PolicySpec("x5", x=0.05, off_level=0.3),
                           PolicySpec("x10", x=0.10, off_level=0.3)])
        b = grid.n_rows
        problem = problem_from_grid(grid)
        problem = problem._replace(
            prices=jnp.asarray(problem.prices, jnp.float64),
            price_sum=jnp.asarray(problem.price_sum, jnp.float64))
        coupling = dispatch_coupling_from_grid(
            grid, DispatchConfig(demand_frac=0.4, migrate_cost=3.0,
                                 min_dwell_h=2))
        r = np.random.default_rng(11)
        raw = PolicyParams(raw_off=jnp.asarray(r.uniform(70, 110, b)),
                           raw_gap=jnp.asarray(r.uniform(0.5, 3.0, b)),
                           raw_lvl=jnp.asarray(r.uniform(-1.0, 1.0, b)))

        def loss(rw):
            return soft_objective(rw, problem, 4.0, dispatch=coupling,
                                  dispatch_min_dwell=2, fused=False)[0]

        got = jax.grad(loss)(raw)
        worst = 0.0
        for field in raw._fields:
            base = np.asarray(getattr(raw, field), np.float64)
            for i in range(b):
                h = 1e-5 * max(1.0, abs(base[i]))
                hi, lo = base.copy(), base.copy()
                hi[i] += h
                lo[i] -= h
                fd = (loss(raw._replace(**{field: jnp.asarray(hi)}))
                      - loss(raw._replace(**{field: jnp.asarray(lo)}))
                      ) / (2 * h)
                ad = float(np.asarray(getattr(got, field))[i])
                worst = max(worst, abs(ad - float(fd))
                            / max(abs(float(fd)), 1e-8))
    return worst


def bench_tune_dispatch(n_markets: int = 4, hours: int = 1024,
                        steps: int = 60, with_fd: bool = True) -> dict:
    """A/B dispatch-aware tuning vs the PR-3 re-score-only path on a
    one-policy-per-site fleet, both hard-scored on feasible
    `repro.dispatch.dispatch`; plus the FD-gradient correctness margin.

    Headline: ``dispatch_cpc_edge`` = re-score-only fleet CPC divided
    by the dispatch-aware fleet CPC (>= 1 means differentiating through
    dispatch paid for itself on this fixed-seed fleet)."""
    markets = [region_params("germany", seed=s).replace(n_hours=hours)
               for s in range(n_markets)]
    p_avg = markets[0].p_avg
    systems = [make_system(0.5 * hours * 1.0 * p_avg, 1.0, float(hours))]
    grid = build_grid(markets, systems,
                      [PolicySpec("x8", x=0.08, off_level=0.3)])
    dcfg = DispatchConfig(demand_frac=0.25, migrate_cost=4.0,
                          min_dwell_h=3)

    import time
    t0 = time.perf_counter()
    rescore = optimize(grid, TuneConfig(steps=steps, dispatch=dcfg))
    t_rescore = time.perf_counter() - t0
    t0 = time.perf_counter()
    aware = optimize(grid, TuneConfig(steps=steps, dispatch_soft=dcfg))
    t_aware = time.perf_counter() - t0

    cpc_rescore = min(rescore.dispatch["cpc_tuned"],
                      rescore.dispatch["cpc_swept"])
    cpc_aware = min(aware.dispatch["cpc_tuned"],
                    aware.dispatch["cpc_swept"])
    out = {
        "rows": grid.n_rows,
        "hours": hours,
        "steps": steps,
        "cpc_rescore": cpc_rescore,
        "cpc_aware": cpc_aware,
        "dispatch_cpc_edge": cpc_rescore / cpc_aware,
        "wall_s_rescore": t_rescore,
        "wall_s_aware": t_aware,
        "chosen_rescore": rescore.dispatch["chosen"],
        "chosen_aware": aware.dispatch["chosen"],
    }
    if with_fd:
        worst = fd_grad_worst_rel_err()
        out["fd_grad_worst_rel_err"] = worst
        # margin vs the 1e-3 contract, capped at 10: the raw worst
        # error is FD-cancellation noise (~1e-6), so an uncapped ratio
        # would gate on that noise ~500x inside the contract — capped,
        # every healthy run reports exactly 10 (worst <= 1e-4) and the
        # low-water gate trips only when the error nears the contract,
        # while a real implicit-gradient bug (errors of 1e-2+) still
        # collapses the margin by orders of magnitude
        out["fd_grad_margin"] = min(10.0, 1e-3 / max(worst, 1e-12))
    write_artifact("bench_tune_dispatch", out)
    return out


ALL = {"bench_tune": bench_tune,
       "bench_tune_dispatch": bench_tune_dispatch}
