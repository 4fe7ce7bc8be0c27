"""Benchmark aggregator: one entry per paper figure/table + runtime
benches + the roofline table (if dry-run artifacts exist).

  PYTHONPATH=src python -m benchmarks.run [--only fig3_pv_intervals]
"""

from __future__ import annotations

import argparse
import time
import traceback

from benchmarks import (bench_dispatch, bench_faults, bench_fleet,
                        bench_live,
                        bench_runtime, bench_tune, bench_tune_coupled,
                        bench_workload, paper_figures)
from benchmarks.common import ARTIFACTS
from repro.compile_cache import use_compile_cache


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-runtime", action="store_true",
                    help="paper figures only (fast)")
    args = ap.parse_args()
    use_compile_cache()

    suites = dict(paper_figures.ALL)
    if not args.skip_runtime:
        suites.update(bench_fleet.ALL)
        suites.update(bench_dispatch.ALL)
        suites.update(bench_tune.ALL)
        suites.update(bench_tune_coupled.ALL)
        suites.update(bench_live.ALL)
        suites.update(bench_faults.ALL)
        suites.update(bench_workload.ALL)
        suites.update(bench_runtime.ALL)
    if args.only:
        suites = {k: v for k, v in suites.items() if k == args.only}

    failures = 0
    print(f"{'benchmark':28s} {'seconds':>8s}  headline")
    for name, fn in suites.items():
        t0 = time.perf_counter()
        try:
            out = fn()
            dt = time.perf_counter() - t0
            headline = _headline(name, out)
            print(f"{name:28s} {dt:8.2f}  {headline}")
        except Exception as e:                      # pragma: no cover
            failures += 1
            print(f"{name:28s} {'FAIL':>8s}  {type(e).__name__}: {e}")
            traceback.print_exc()

    # roofline (only if the dry-run has produced artifacts)
    dryrun = ARTIFACTS / "dryrun" / "pod16x16"
    if dryrun.exists() and any(dryrun.glob("*.json")):
        from benchmarks.roofline import load_rows
        rows = load_rows("pod16x16")
        n_fit = sum(r.fits for r in rows)
        bounds = {b: sum(1 for r in rows if r.bound == b)
                  for b in ("compute", "memory", "collective")}
        print(f"{'roofline(pod16x16)':28s} {'-':>8s}  "
              f"{len(rows)} cells, {n_fit} fit, bounds: {bounds}")
    print(f"artifacts -> {ARTIFACTS}")
    return 1 if failures else 0


def _headline(name: str, out: dict) -> str:
    if name == "fig3_pv_intervals":
        h = out["intervals"]["1h"]
        return (f"x_BE(1h)={h['x_be_pct']:.2f}% "
                f"(paper {out['paper']['x_be_pct_1h']}%), "
                f"weekly viable={out['intervals']['1w']['viable']}")
    if name == "fig4_de_vs_sa":
        return (f"x_BE DE={out['germany']['x_be_pct']:.1f}% "
                f"SA={out['south_australia']['x_be_pct']:.1f}% "
                f"(paper 3.3/25.7)")
    if name == "fig5_psi_sweep":
        psi8 = out.get("psi_for_8pct")
        return (f"Psi for 8% reduction: "
                f"{psi8:.2f}" if psi8 else "8% never reached"
                ) + f" (paper ~{out['paper_psi_for_8pct']})"
    if name == "fig6_combined":
        c = out["amplified+cheap_hw"]
        return (f"combined x_BE={c['x_be_pct']:.1f}% "
                f"x_opt={c['x_opt_pct']:.2f}% (paper 10.15/2.77)")
    if name == "table2_regions":
        import numpy as np
        errs = [abs(v["ours"]["x_be_pct"] - v["paper"]["x_be_pct"])
                for v in out.values()
                if v["paper"]["x_be_pct"] and v["ours"]["x_be_pct"]]
        return f"{len(out)} regions, mean |x_BE err| = {np.mean(errs):.2f}pp"
    if name == "energy_aware_training":
        return (f"CPC red: predicted {out['predicted_cpc_red_pct']:.2f}% "
                f"realized {out['realized_cpc_red_pct']:.2f}%")
    if name == "fig1_diurnal":
        return (f"evening - midday = {out['evening_minus_midday']:.1f} "
                "EUR/MWh")
    if name == "fig2_price_regions":
        return f"p_thresh(x=1.15%) = {out['p_thresh']:.1f} EUR/MWh"
    if name == "bench_fleet":
        return (f"{out['rows']} rows: {out['rows_per_s_vectorized']:.0f} "
                f"rows/s vectorized vs {out['rows_per_s_python_loop']:.1f} "
                f"per-row loop (x{out['speedup']:.0f})")
    if name == "bench_dispatch":
        return (f"{out['sites']} sites x {out['hours']} h: "
                f"{out['hours_per_s_fused']:.0f} h/s fused vs "
                f"{out['hours_per_s_python_loop']:.1f} per-hour loop "
                f"(x{out['speedup']:.0f}), pallas|ref err "
                f"{out['max_abs_err_pallas_vs_ref']:.1e}")
    if name == "bench_faults":
        return (f"{out['rows']} rows: zero-fault ratio "
                f"{out['fault_mask_speed_ratio']:.2f}, storm ratio "
                f"{out['fault_storm_speed_ratio']:.2f}, masked "
                f"bit-identical: {out['bit_identical_masked_zero_fault']}")
    if name == "bench_workload":
        return (f"{out['rows']} rows x {out['n_draws']} draws: "
                f"short-circuit ratio "
                f"{out['workload_short_circuit_ratio']:.2f}, coupled "
                f"ratio {out['workload_coupled_speed_ratio']:.2f}, "
                f"fleet half bit-identical: "
                f"{out['bit_identical_coupled_fleet_report']}")
    if name == "bench_tune":
        line = (f"{out['rows']} rows x {out['steps']} steps: "
                f"{out['row_steps_per_s_fused']:.0f} row-steps/s fused "
                f"vs {out['row_steps_per_s_native']:.0f} native "
                f"(x{out['speedup_fused_vs_native']:.1f})")
        if out.get("temp_reduction"):
            line += f", x{out['temp_reduction']:.1f} less scratch"
        if "rows_strictly_better" in out:
            line += (f"; {out['rows_strictly_better']}/{out['rows']} "
                     f"rows beat best swept")
        return line
    if name == "bench_tune_dispatch":
        return (f"{out['rows']} sites x {out['hours']} h: fleet CPC "
                f"aware {out['cpc_aware']:.2f} vs rescore "
                f"{out['cpc_rescore']:.2f} "
                f"(edge x{out['dispatch_cpc_edge']:.4f}), FD-grad "
                f"margin {out['fd_grad_margin']:.0f}")
    if name == "bench_tune_coupled":
        return (f"dispatch VJP bwd x{out['speedup_dispatch_vjp']:.1f} "
                f"fused-vs-native (S={out['sites']}, B={out['batch']}); "
                f"{out['rows']} rows / {out['n_shards']} shards: "
                f"err {out['err_ulp']:.1f} ULP "
                f"({'OK' if out['coupled_shard_ulp_ok'] else 'FAIL'})")
    if name == "bench_live":
        return (f"{out['rows']} controllers x {out['hours']} h: "
                f"{out['controller_hours_per_s_jitted']:.0f} ctrl-h/s "
                f"jitted vs {out['controller_hours_per_s_python']:.0f} "
                f"python re-plan (x{out['speedup_live']:.0f})")
    if name == "step_time":
        return ", ".join(f"{k}: {v['s_per_step']:.2f}s"
                         for k, v in out.items())
    return ""


if __name__ == "__main__":
    raise SystemExit(main())
