"""The plain references agree with the program at tiny sizes on the CPU
(Pallas kernels in interpret mode)."""

import numpy as np
import pytest

from chipbench import data
from chipbench.reference import fleet as fref

HOURS = 96
CONFIG = {"hours": HOURS, "markets": ["germany", "france"],
          "block_hours": 168, "psi_market": "germany",
          "psi_range": [0.5, 4.0, 2], "ratings_mw": [1.0, 2.0],
          "shutdown_share_range": [0.0, 0.3],
          "policy_kinds": [{}, {"hysteresis": 0.9}, {"off_level": 0.3},
                           {"idle_frac": 0.05, "restart_energy_mwh": 0.3,
                            "restart_time_h": 0.25}]}


def _inputs(seed=7):
    prices = data.market_years(CONFIG, seed, 2, True)
    specs = data.system_specs(CONFIG, {})
    p_avg = data.psi_price(CONFIG)
    table = data.policy_table(CONFIG, data.shares(CONFIG, seed, 2, 0, 3))
    return prices, specs, p_avg, table


@pytest.mark.parametrize("use_pallas", [True, False])
def test_sweep_reference_matches_backtest(use_pallas):
    from repro.fleet import backtest, build_grid
    prices, specs, p_avg, table = _inputs()
    grid = build_grid(prices, data.systems(specs, HOURS, p_avg),
                      data.policy_specs(table))
    rep = backtest(grid, use_pallas=use_pallas)
    want = fref.sweep(prices, [(psi * HOURS * mw * p_avg, mw, HOURS)
                               for psi, mw in specs], table)
    np.testing.assert_array_equal(np.asarray(rep.n_starts),
                                  want["n_starts"])
    for f in ("cpc", "tco", "up_hours"):
        np.testing.assert_allclose(np.asarray(getattr(rep, f)), want[f],
                                   rtol=1e-5)


def test_tune_reference_matches_reported_cpc():
    from chipbench.entries.tune import Entry
    from chipbench.run import Spans
    cell = {"years_per_market": 1, "bootstrap": True, "psi_count": 2,
            "ratings_mw": [1.0], "shares_per_call": 2}
    e = Entry({**CONFIG, "psi_range": [0.5, 4.0, 2]}, cell, 11, Spans())
    out = e.call(data.WINDOW, 0)
    r = e.readings(0, out)
    assert r["cpc_rel"] < 1e-5
    assert r["gain_pct"] > 0.0

