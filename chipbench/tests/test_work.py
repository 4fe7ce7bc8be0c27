"""Roofline work comes from the problem's shapes, the same whichever path
implements it; peaks come from the benchmark's own table."""

import pytest

from chipbench import data, peaks
from chipbench.work import fleet_scan


def test_fleet_work_same_for_pallas_and_xla():
    from repro.fleet import backtest, build_grid
    cfg = {"hours": 48, "markets": ["germany"], "block_hours": 168,
           "psi_market": "germany", "policy_kinds": [{}, {"off_level": 0.3}]}
    prices = data.market_years(cfg, 3, 2, True)
    grid = build_grid(prices, data.systems([(1.0, 1.0), (2.0, 1.0)], 48,
                                           80.0),
                      data.policy_specs(data.policy_table(cfg, [0.1])))
    counts = set()
    for use_pallas in (True, False):
        rep = backtest(grid, use_pallas=use_pallas)
        counts.add(fleet_scan.work(int(rep.cpc.shape[0]), grid.n_hours,
                                      grid.n_markets))
    assert len(counts) == 1
    ops, nbytes = counts.pop()
    assert ops == fleet_scan.OPS_PER_ROW_HOUR * grid.n_rows * 48
    assert nbytes == 4.0 * (2 * 48 + grid.n_rows * 21)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peaks("TPU v99")
    assert peaks.least_seconds(197e12, 0.0, "TPU v5 lite") == 1.0
    assert peaks.least_seconds(0.0, 819e9, "TPU v5 lite") == 1.0
