"""Single-jit fleet backtest: every scenario row, every hour, one call.

The engine replaces the per-trace Python loops of `examples/*.py` with one
jitted pass: the stateful hysteresis/partial-capacity scan runs batched
over all B rows and cost accounting — restart overheads, idle draw, lost
restart time included — is a handful of fused [B] vector ops. A 1024-row x
8760-hour grid is a single dispatch. On TPU the Pallas `fleet_scan` reads
each row's prices from the [N, T] market block by the row's market index,
inside the kernel, so no [B, T] copy of the prices is written (only
per-hour telemetry's companion scan gathers one); elsewhere the pure-JAX
`fleet_scan_ref` recurrence runs over a [B, T] gather of the rows'
prices.

Row semantics match `repro.core.policy.policy_cpc` (B=1 with
``off_level=0`` reproduces it to float round-off). Boundary convention:
the row state machine resumes on ``p <= p_on``, so a degenerate
``p_on == p_off`` row is *exactly* `threshold_policy` (whose thresholds
are price samples, making p == p_off common); a proper hysteresis row
differs from `hysteresis_policy` (strict ``p < p_on``) only at samples
exactly equal to p_on — a non-sample value in practice. Monte-Carlo
market ensembles give confidence bands on the Eq. (19) viability
question for free along the market axis.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.execution import ExecutionPlan
from repro.fleet.grid import ScenarioGrid, concat_rows, row_chunks
from repro.fleet.report import FleetReport
from repro.kernels.fleet_scan import fleet_scan
from repro.kernels.ref import FleetScanOut, fleet_hourly_ref, fleet_scan_ref
from repro.obs.profiling import profiled


class FleetCosts(NamedTuple):
    """Per-row cost assembly over a `FleetScanOut` (all [B])."""

    cpc: jax.Array          # realized cost-per-compute
    cpc_ao: jax.Array       # always-on baseline (Eq. 11)
    tco: jax.Array          # fixed + energy + restart cost
    energy_cost: jax.Array  # running + idle draw energy cost
    restart_cost: jax.Array
    up_hours: jax.Array


def fleet_costs(scan: FleetScanOut, *, price_sum, fixed, power, period,
                restart_energy_mwh, restart_time_h, n_samples: int
                ) -> FleetCosts:
    """Cost accounting shared by the hard backtest and the differentiable
    tuner (`repro.tune.objective`): every quantity is affine in the four
    scan sums, so the same closed form prices a hard *and* a soft scan.
    ``price_sum`` is sum_t p_t per row; ``n_samples`` the series length.
    """
    dt = period / n_samples                           # [B] hours per sample
    e_ao = dt * power * price_sum                     # E_AO (Eq. 6)
    e_run = dt * power * scan.draw_price_sum
    e_restart = restart_energy_mwh * scan.restart_price_sum
    up_hours = dt * scan.up_units - restart_time_h * scan.n_starts
    tco = fixed + e_run + e_restart
    cpc = tco / jnp.maximum(up_hours, 1e-9)
    cpc_ao = (fixed + e_ao) / period                  # Eq. (11)
    return FleetCosts(cpc=cpc, cpc_ao=cpc_ao, tco=tco, energy_cost=e_run,
                      restart_cost=e_restart, up_hours=up_hours)


@functools.partial(jax.jit, static_argnames=("use_pallas", "block_b",
                                             "block_t", "telemetry"))
def _backtest_jit(prices, market_idx, system_idx, policy_idx,
                  fixed, power, period, p_on, p_off, off_level, idle_frac,
                  restart_energy_mwh, restart_time_h, *,
                  use_pallas: bool, block_b: int, block_t: int,
                  telemetry: bool = False) -> FleetReport:
    t = prices.shape[1]
    with jax.named_scope("backtest.gather"):
        price_sum = jnp.sum(prices, axis=1)[market_idx]   # [B] sum_t p_t

    def rows():
        # [B, T] per-row prices, for the XLA scans only
        with jax.named_scope("backtest.gather"):
            return prices[market_idx]

    if use_pallas:
        scan = fleet_scan(prices, market_idx, p_on, p_off, off_level,
                          idle_frac, block_b=block_b, block_t=block_t)
    else:
        scan = fleet_scan_ref(rows(), p_on, p_off, off_level, idle_frac)

    if telemetry:
        # per-hour decision records: a *companion* scan over the same
        # state machine (`hard_hour_step`), aggregated on-device to [T]
        # and drained once per call — it reads the report's inputs and
        # feeds nothing back, so the FleetReport bits cannot change
        # (pinned in tests/test_obs.py)
        hourly = fleet_hourly_ref(rows(), p_on, p_off, off_level,
                                  idle_frac, power)
        obs.drain("fleet.hourly", on_mw=hourly.on_mw,
                  draw_price=hourly.draw_price, starts=hourly.starts,
                  stops=hourly.stops)

    with jax.named_scope("backtest.costs"):
        costs = fleet_costs(scan, price_sum=price_sum, fixed=fixed,
                            power=power, period=period,
                            restart_energy_mwh=restart_energy_mwh,
                            restart_time_h=restart_time_h, n_samples=t)
        return FleetReport(
            cpc=costs.cpc, cpc_ao=costs.cpc_ao,
            cpc_reduction=1.0 - costs.cpc / costs.cpc_ao,
            tco=costs.tco, energy_cost=costs.energy_cost,
            restart_cost=costs.restart_cost,
            up_hours=costs.up_hours, n_starts=scan.n_starts,
            x_realized=1.0 - scan.up_units / t,
            market_idx=market_idx, system_idx=system_idx,
            policy_idx=policy_idx)


def backtest(grid: ScenarioGrid, *, use_pallas: Optional[bool] = None,
             block_b: int = 128, block_t: int = 512,
             chunk_rows: Optional[int] = None,
             plan: Optional[ExecutionPlan] = None) -> FleetReport:
    """Backtest every scenario row of ``grid`` in one jitted call.

    ``use_pallas=None`` auto-selects: the Pallas kernel on TPU, the
    vectorized pure-JAX recurrence elsewhere (the Pallas interpreter is a
    debugging tool, not a fast path). Both paths are checked against each
    other in `tests/test_fleet.py`.

    ``plan`` (`repro.execution.ExecutionPlan`) chooses the execution
    layout — the same object `repro.tune.TuneConfig` takes. A chunked
    plan evaluates the grid in fixed-size row slices (via
    `ScenarioGrid.take_rows`, padded to one compile shape) instead of
    one pass — per-row results are identical, but the XLA path's
    in-jit [B, T] price gather never exceeds the chunk footprint, which
    is what lets `repro.tune.optimize` hard-re-evaluate B ~ 10^5 grids
    on one host (the Pallas path gathers no [B, T] prices; it holds
    [B]-sized arrays and the [N, T] table alone).
    ``mode='sharded'`` raises: the backtest is a single [B, T] map with
    no coupled terms, so chunking already covers its memory story and a
    shard_map path would only add a second numerics contract.
    ``chunk_rows`` is the deprecated spelling of a chunked plan (one
    release of `DeprecationWarning`, then removal).
    """
    if chunk_rows is not None:
        if plan is not None:
            raise ValueError("backtest: pass plan= or the deprecated "
                             "chunk_rows, not both")
        warnings.warn(
            "backtest(chunk_rows=...) is deprecated — pass "
            "plan=repro.execution.ExecutionPlan(mode='chunked', "
            "chunk_rows=..., contract='bitwise') instead",
            DeprecationWarning, stacklevel=2)
        plan = ExecutionPlan(mode="chunked", chunk_rows=chunk_rows,
                             contract="bitwise") if chunk_rows \
            else ExecutionPlan(mode="single")
    if plan is not None and plan.mode == "sharded":
        raise ValueError(
            "backtest does not shard: the hard backtest is an uncoupled "
            "per-row map, so use ExecutionPlan(mode='chunked') for the "
            "memory bound (bitwise-identical results) instead")
    chunk = plan.chunk_rows if plan is not None else 0
    if chunk and grid.n_rows > chunk:
        parts = [backtest(grid.take_rows(sl), use_pallas=use_pallas,
                          block_b=block_b, block_t=block_t)
                 for sl in row_chunks(grid.n_rows, chunk)]
        return concat_rows(parts, grid.n_rows)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    telemetry = obs.enabled()
    with profiled("backtest.launch", rows=grid.n_rows,
                  hours=grid.n_hours):
        report = _backtest_jit(
            grid.prices, grid.market_idx, grid.system_idx,
            grid.policy_idx, grid.fixed, grid.power, grid.period,
            grid.p_on, grid.p_off, grid.off_level, grid.idle_frac,
            grid.restart_energy_mwh, grid.restart_time_h,
            use_pallas=bool(use_pallas), block_b=block_b, block_t=block_t,
            telemetry=telemetry)
    if telemetry:
        obs.counter("fleet.backtests").inc()
        obs.trace_event("fleet.backtest", {
            "rows": int(grid.n_rows), "hours": int(grid.prices.shape[1]),
            "use_pallas": bool(use_pallas),
            "n_starts_total": float(jnp.sum(report.n_starts)),
            "cpc_mean": float(jnp.mean(report.cpc)),
            "reduction_mean": float(jnp.mean(report.cpc_reduction))})
    return report
