"""Telemetry subsystem tests (`repro.obs`): the zero-perturbation
contract (every numeric result bit-identical with telemetry on vs off,
and the disabled program stages no host callbacks at all), exact
counter-vs-oracle agreement (the trace reproduces
`FleetSummary.dispatch`'s move count and CPC bit for bit), the
loader-event payload contract, the profiling capture, and a golden-file
test of the ``python -m repro.obs.report`` digest.

Regenerate the golden digest after an intentional format change with

  REGEN_OBS_GOLDEN=1 PYTHONPATH=src python -m pytest \\
      tests/test_obs.py::test_report_digest_matches_golden -q
"""

import json
import os
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.tco import make_system
from repro.dispatch import DispatchConfig, build_problem, dispatch
from repro.energy.presets import region_params
from repro.energy.smard import load_price_csv
from repro.fleet import PolicySpec, backtest, build_grid, summarize
from repro.fleet.engine import _backtest_jit
from repro.obs.profiling import profiled, record_compiled, xla_trace
from repro.obs.report import (load_events, load_metrics,
                              reconstruct_dispatch, render_digest)
from repro.tune import TuneConfig, optimize

GOLDEN = Path(__file__).resolve().parent / "golden" / "obs_digest.md"


def _grid(t: int = 240, n_markets: int = 2):
    """Fixed-seed grid whose policies keep partial capacity online
    (off_level > 0), so the 35%-of-ratings dispatch demand below stays
    feasible in every hour."""
    markets = [region_params("germany", seed=s).replace(n_hours=t)
               for s in range(n_markets)]
    p_avg = markets[0].p_avg
    systems = [make_system(2.0 * t * 1.0 * p_avg, 1.0, float(t))]
    policies = [PolicySpec("always_on"),
                PolicySpec("x5", x=0.05, off_level=0.4),
                PolicySpec("x10", x=0.10, off_level=0.4),
                PolicySpec("x20", x=0.20, off_level=0.4)]
    return build_grid(markets, systems, policies,
                      market_names=[f"de-seed{s}" for s in range(n_markets)],
                      system_names=["psi2.0"])


_DCFG = DispatchConfig(demand_frac=0.35, migrate_cost=2.0, min_dwell_h=2)


def _assert_tree_equal(got, want, what: str) -> None:
    for field in want._fields:
        g, w = getattr(got, field), getattr(want, field)
        if g is None or w is None:
            assert g is w, f"{what}.{field}"
            continue
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"{what}.{field}")


# ---------------------------------------------------------------------------
# (a) bit-identity: telemetry on vs off changes no numeric result
# ---------------------------------------------------------------------------

def test_backtest_bit_identical_on_off(tmp_path):
    grid = _grid()
    off = backtest(grid, use_pallas=False)
    with obs.capture(tmp_path / "run"):
        on = backtest(grid, use_pallas=False)
    _assert_tree_equal(on, off, "FleetReport")
    # and the run actually observed something
    kinds = {e["kind"] for e in load_events(tmp_path / "run")}
    assert {"fleet.backtest", "fleet.hourly"} <= kinds


def test_backtest_bit_identical_on_off_x64(tmp_path):
    with jax.enable_x64(True):
        grid = _grid(t=120)
        off = backtest(grid, use_pallas=False)
        with obs.capture(tmp_path / "run"):
            on = backtest(grid, use_pallas=False)
        _assert_tree_equal(on, off, "FleetReport[x64]")


def test_dispatch_bit_identical_on_off(tmp_path):
    grid = _grid()
    rep = backtest(grid, use_pallas=False)
    off = summarize(grid, rep, dispatch_cfg=_DCFG).dispatch
    with obs.capture(tmp_path / "run"):
        on = summarize(grid, rep, dispatch_cfg=_DCFG).dispatch
    _assert_tree_equal(on, off, "DispatchResult")


def test_optimize_bit_identical_on_off(tmp_path):
    grid = _grid(t=160)
    cfg = TuneConfig(steps=10, shard=False)
    off = optimize(grid, cfg)
    with obs.capture(tmp_path / "run"):
        on = optimize(grid, cfg)
    for field in ("cpc", "cpc_tuned", "cpc_swept", "cpc_swept_best",
                  "source", "stage_cpc"):
        np.testing.assert_array_equal(np.asarray(getattr(on, field)),
                                      np.asarray(getattr(off, field)),
                                      err_msg=field)
    _assert_tree_equal(on.raw, off.raw, "raw")
    _assert_tree_equal(on.params, off.params, "params")
    for k in off.history:
        np.testing.assert_array_equal(np.asarray(on.history[k]),
                                      np.asarray(off.history[k]),
                                      err_msg=f"history[{k}]")


def test_optimize_bit_identical_on_off_x64(tmp_path):
    with jax.enable_x64(True):
        grid = _grid(t=120)
        cfg = TuneConfig(steps=6, shard=False)
        off = optimize(grid, cfg)
        with obs.capture(tmp_path / "run"):
            on = optimize(grid, cfg)
        np.testing.assert_array_equal(on.cpc, off.cpc)
        np.testing.assert_array_equal(on.cpc_tuned, off.cpc_tuned)
        _assert_tree_equal(on.raw, off.raw, "raw[x64]")


def test_optimize_bit_identical_on_off_acceptance_grid(tmp_path):
    """The PR's acceptance grid (the same fixed-seed 256-row grid
    test_tune.py's guarantee runs on): enabling telemetry must leave the
    entire tuned result bit-identical."""
    from repro.energy.markets import MarketParams
    t = 600
    markets = [MarketParams(n_hours=t, seed=s) for s in range(4)]
    systems = [make_system(float(psi) * t * 1.0 * 80.0, 1.0, float(t))
               for psi in (0.5, 1.0, 2.0, 4.0)]
    xs = (0.01, 0.02, 0.03, 0.05, 0.08, 0.10, 0.12, 0.15,
          0.20, 0.25, 0.30, 0.40)
    policies = [PolicySpec("ao")] + \
        [PolicySpec(f"x{int(x * 100)}", x=x) for x in xs] + \
        [PolicySpec("x3h", x=0.03, hysteresis=0.9),
         PolicySpec("x8h", x=0.08, hysteresis=0.85),
         PolicySpec("x15h", x=0.15, hysteresis=0.9)]
    grid = build_grid(markets, systems, policies)
    assert grid.n_rows == 256
    cfg = TuneConfig(steps=25)
    off = optimize(grid, cfg)
    with obs.capture(tmp_path / "run"):
        on = optimize(grid, cfg)
    np.testing.assert_array_equal(on.cpc, off.cpc)
    np.testing.assert_array_equal(on.cpc_tuned, off.cpc_tuned)
    np.testing.assert_array_equal(on.stage_cpc, off.stage_cpc)
    _assert_tree_equal(on.raw, off.raw, "raw")
    _assert_tree_equal(on.params, off.params, "params")


# ---------------------------------------------------------------------------
# (b) the disabled program stages no host callbacks at all
# ---------------------------------------------------------------------------

def test_disabled_program_has_no_callbacks(tmp_path):
    grid = _grid(t=64)
    args = (grid.prices, grid.market_idx, grid.system_idx,
            grid.policy_idx, grid.fixed, grid.power, grid.period,
            grid.p_on, grid.p_off, grid.off_level, grid.idle_frac,
            grid.restart_energy_mwh, grid.restart_time_h)

    def trace(telemetry):
        return str(jax.make_jaxpr(
            lambda *a: _backtest_jit(*a, use_pallas=False, block_b=128,
                                     block_t=512, telemetry=telemetry)
        )(*args))

    assert not obs.enabled()
    assert "io_callback" not in trace(False)
    with obs.capture(tmp_path / "run"):
        assert "io_callback" in trace(True)
        # ... and telemetry=False stages nothing even while a run is on
        assert "io_callback" not in trace(False)


def test_drained_program_goes_quiet_after_disable(tmp_path):
    """A program compiled with its telemetry callback staged stops
    writing the moment the run closes — the io_callback sink looks the
    run up at call time, no retrace needed."""
    grid = _grid(t=64)
    args = (grid.prices, grid.market_idx, grid.system_idx,
            grid.policy_idx, grid.fixed, grid.power, grid.period,
            grid.p_on, grid.p_off, grid.off_level, grid.idle_frac,
            grid.restart_energy_mwh, grid.restart_time_h)
    with obs.capture(tmp_path / "run"):
        jax.block_until_ready(_backtest_jit(
            *args, use_pallas=False, block_b=128, block_t=512,
            telemetry=True))
        n_live = len(load_events(tmp_path / "run"))
    assert n_live >= 2                       # run.meta + fleet.hourly
    # same compiled entry, run closed: must not raise, must not write
    jax.block_until_ready(_backtest_jit(
        *args, use_pallas=False, block_b=128, block_t=512,
        telemetry=True))
    events = load_events(tmp_path / "run")
    assert sum(e["kind"] == "fleet.hourly" for e in events) == 1


# ---------------------------------------------------------------------------
# (c) counter vs oracle: the trace reproduces the dispatch result exactly
# ---------------------------------------------------------------------------

def test_trace_reproduces_dispatch_result_exactly(tmp_path):
    grid = _grid()
    rep = backtest(grid, use_pallas=False)
    with obs.capture(tmp_path / "run"):
        summ = summarize(grid, rep, dispatch_cfg=_DCFG)
    oracle = summ.dispatch
    events = load_events(tmp_path / "run")

    result = [e for e in events if e["kind"] == "dispatch.result"][-1]
    assert result["cpc"] == oracle.cpc
    assert result["n_migrations"] == oracle.n_migrations
    assert result["energy_cost"] == oracle.energy_cost
    assert result["migration_cost"] == oracle.migration_cost
    assert result["slack_capacity_mw"] == oracle.slack_capacity_mw
    assert result["slack_power_mw"] == oracle.slack_power_mw
    assert result["slack_floor_mwh"] == oracle.slack_floor_mwh

    # reconstruction from the per-hour event alone — not the scalars
    recon = reconstruct_dispatch(events)
    assert recon["cpc"] == oracle.cpc
    assert recon["n_migrations"] == oracle.n_migrations
    assert recon["energy_cost"] == oracle.energy_cost
    assert recon["migration_cost"] == oracle.migration_cost
    assert recon["delivered_mwh"] == oracle.delivered_mwh
    assert recon["slack_capacity_mw"] == oracle.slack_capacity_mw

    # and the metric instruments agree with both
    metrics = load_metrics(tmp_path / "run")
    assert metrics["counters"]["dispatch.calls"] == 1
    assert metrics["counters"]["dispatch.moves"] == oracle.n_migrations
    assert metrics["gauges"]["dispatch.cpc"] == oracle.cpc


def test_infeasible_dispatch_emits_reasoned_event(tmp_path):
    grid = _grid(t=96)
    rep = backtest(grid, use_pallas=False)
    bad = DispatchConfig(demand_frac=0.35, power_cap_mw=1e-3)
    with obs.capture(tmp_path / "run"):
        from repro.dispatch import DispatchInfeasible
        with pytest.raises(DispatchInfeasible):
            summarize(grid, rep, dispatch_cfg=bad)
        events = [e for e in load_events(tmp_path / "run")
                  if e["kind"] == "dispatch.infeasible"]
    assert len(events) == 1
    assert events[0]["constraint"] == "power_cap"


def test_tune_trace_matches_result(tmp_path):
    grid = _grid(t=160)
    cfg = TuneConfig(steps=10, shard=False)
    with obs.capture(tmp_path / "run"):
        res = optimize(grid, cfg)
    events = load_events(tmp_path / "run")
    steps = [e for e in events if e["kind"] == "tune.step"]
    stages = [e for e in events if e["kind"] == "tune.stage"]
    result = [e for e in events if e["kind"] == "tune.result"][-1]
    assert len(steps) == cfg.steps
    assert [e["step"] for e in steps] == list(range(cfg.steps))
    assert all("grad_norm" in e and "clip_frac" in e for e in steps)
    np.testing.assert_array_equal(
        np.asarray([e["loss"] for e in steps]),
        np.asarray(res.history["loss"], np.float64))
    assert len(stages) == TuneConfig().eval_stages
    np.testing.assert_array_equal(
        np.asarray([e["cpc_hard_mean"] for e in stages]), res.stage_cpc)
    assert stages[-1]["through_step"] == cfg.steps
    assert result["rows"] == grid.n_rows
    assert result["cpc_mean"] == float(np.mean(res.cpc))
    assert sum(result["source_counts"].values()) == grid.n_rows


# ---------------------------------------------------------------------------
# (d) loader events mirror LoadStats exactly
# ---------------------------------------------------------------------------

def test_loader_event_payload_matches_loadstats(tmp_path):
    csv = tmp_path / "prices.csv"
    csv.write_text("price\n80.0\n81.5\nnot-a-number\n79.0\nbad\n82.0\n")
    with obs.capture(tmp_path / "run"):
        with pytest.warns(UserWarning):
            _, stats = load_price_csv(csv, return_stats=True,
                                      max_skip_frac=0.05)
    events = [e for e in load_events(tmp_path / "run")
              if e["kind"] == "loader.skipped_rows"]
    assert len(events) == 1
    e = events[0]
    assert e["action"] == "warn"
    assert e["loader"] == "load_price_csv"
    assert e["path"] == str(csv)
    for field in ("n_rows", "n_parsed", "n_skipped", "n_nan"):
        assert e[field] == getattr(stats, field), field
    assert e["skip_frac"] == stats.skip_frac
    metrics = load_metrics(tmp_path / "run")
    assert metrics["counters"]["loader.skipped_rows"] == \
        stats.n_skipped + stats.n_nan


def test_loader_silent_when_disabled(tmp_path):
    csv = tmp_path / "prices.csv"
    csv.write_text("80.0\nbad\n82.0\n" * 10)
    assert not obs.enabled()
    with pytest.warns(UserWarning):
        arr = load_price_csv(csv, max_skip_frac=0.05)
    assert arr.shape == (20,)


# ---------------------------------------------------------------------------
# (e) profiling capture
# ---------------------------------------------------------------------------

def test_profiling_span_and_compiled_analysis(tmp_path):
    with obs.capture(tmp_path / "run"):
        with profiled("unit.block", rows=3):
            pass
        compiled = jax.jit(lambda x: (x * 2.0).sum()).lower(
            np.ones((8, 8), np.float32)).compile()
        payload = record_compiled("unit.program", compiled)
    assert payload["label"] == "unit.program"
    events = load_events(tmp_path / "run")
    spans = [e for e in events if e["kind"] == "profile.span"]
    xla = [e for e in events if e["kind"] == "profile.xla"]
    assert spans[0]["label"] == "unit.block"
    assert spans[0]["rows"] == 3
    assert spans[0]["seconds"] >= 0.0
    assert xla[0]["label"] == "unit.program"


def test_profiling_noops_when_disabled():
    assert not obs.enabled()
    with profiled("nope"):
        pass
    with xla_trace("nope") as d:
        assert d is None
    compiled = jax.jit(lambda x: x + 1).lower(np.ones(4, np.float32)
                                              ).compile()
    payload = record_compiled("nope", compiled)
    assert payload["label"] == "nope"        # returns data, writes nowhere


# ---------------------------------------------------------------------------
# (f) the operator digest (golden file, seeded 8-row run)
# ---------------------------------------------------------------------------

def _golden_run(run_dir) -> None:
    """One seeded end-to-end run exercising every digest section."""
    csv = run_dir.parent / "prices_golden.csv"
    csv.write_text("price\n80.0\n81.5\nbad-row\n79.0\n82.0\n77.5\n"
                   "76.0\n84.0\n")
    with obs.capture(run_dir, run_id="golden"):
        load_price_csv(csv, max_skip_frac=0.5)
        grid = _grid()
        with profiled("tune.optimize", rows=grid.n_rows, steps=12):
            optimize(grid, TuneConfig(steps=12, shard=False))
        rep = backtest(grid, use_pallas=False)
        summarize(grid, rep, dispatch_cfg=_DCFG)


def test_report_digest_matches_golden(tmp_path):
    run_dir = tmp_path / "run"
    _golden_run(run_dir)
    digest = render_digest(run_dir, redact_meta=True)
    if os.environ.get("REGEN_OBS_GOLDEN"):
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_text(digest)
        pytest.skip(f"regenerated {GOLDEN}")
    assert GOLDEN.exists(), \
        "golden digest missing — run with REGEN_OBS_GOLDEN=1 to create"
    assert digest == GOLDEN.read_text(), (
        "digest drifted from tests/golden/obs_digest.md — if the change "
        "is intentional, regenerate with REGEN_OBS_GOLDEN=1")


def test_report_cli_validates_clean(tmp_path, capsys):
    from repro.obs.report import main
    run_dir = tmp_path / "run"
    _golden_run(run_dir)
    out = tmp_path / "digest.md"
    rc = main([str(run_dir), "--validate", "-o", str(out)])
    assert rc == 0
    text = out.read_text()
    assert text.startswith("# Telemetry run digest")
    assert "(matches emitted result exactly)" in text


# ---------------------------------------------------------------------------
# (g) registry plumbing
# ---------------------------------------------------------------------------

def test_capture_restores_disabled_state_on_error(tmp_path):
    with pytest.raises(RuntimeError):
        with obs.capture(tmp_path / "run"):
            assert obs.enabled()
            raise RuntimeError("boom")
    assert not obs.enabled()
    # the run still closed cleanly: metrics.json exists, run.close logged
    events = load_events(tmp_path / "run")
    assert events[-1]["kind"] == "run.close"
    assert (tmp_path / "run" / "metrics.json").exists()


def test_trace_lines_are_schema_stamped_and_ordered(tmp_path):
    with obs.capture(tmp_path / "run"):
        obs.trace_event("tune.step", {"step": 0, "loss": 1.0})
        obs.trace_event("tune.step", {"step": 1, "loss": 0.5})
    events = load_events(tmp_path / "run")
    assert events[0]["kind"] == "run.meta"
    assert all(e["schema"] == 1 for e in events)
    assert [e["seq"] for e in events] == list(range(len(events)))
    meta = events[0]
    for key in ("run_id", "git_sha", "jax", "jaxlib", "backend",
                "timestamp"):
        assert key in meta
    # disabled instruments are throwaways, not errors
    obs.counter("x").inc()
    obs.gauge("x").set(1.0)
    obs.histogram("x").observe(2.0)
    assert json.loads((tmp_path / "run" / "metrics.json").read_text())
