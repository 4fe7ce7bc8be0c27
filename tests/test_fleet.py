"""Fleet backtesting subsystem tests: B=1 equivalence with the
single-trace paths, Pallas kernel vs reference scan, and
permutation-invariant aggregation."""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optimizer import optimal_shutdown
from repro.core.policy import hysteresis_policy, policy_cpc, threshold_policy
from repro.core.tco import make_system
from repro.energy.markets import MarketParams
from repro.fleet import (PolicySpec, backtest, build_grid, elastic_policy,
                         summarize)
from repro.kernels.fleet_scan import fleet_scan, price_planes
from repro.kernels.ref import fleet_scan_ref

rng = np.random.default_rng(7)

T = 1200
SYS = make_system(fixed=60_000.0, power=1.0, period=float(T))


def _grid(policies, n_markets=1, systems=(SYS,)):
    markets = [MarketParams(n_hours=T, seed=s) for s in range(n_markets)]
    return build_grid(markets, list(systems), policies)


# ---------------------------------------------------------------------------
# (a) B=1 rows match the existing single-trace paths
# ---------------------------------------------------------------------------

def test_b1_threshold_matches_policy_cpc():
    grid = _grid([PolicySpec("x3", x=0.03)])
    rep = backtest(grid, use_pallas=False)
    prices = np.asarray(grid.prices[0])
    mask = threshold_policy(prices, float(grid.p_off[0]))
    want = float(policy_cpc(SYS, prices, mask))
    assert float(rep.cpc[0]) == pytest.approx(want, rel=1e-5)
    # realized shutdown fraction equals the mask's off fraction
    assert float(rep.x_realized[0]) == pytest.approx(
        1.0 - float(np.mean(np.asarray(mask))), abs=1e-6)


def test_b1_hysteresis_with_overheads_matches_policy_cpc():
    spec = PolicySpec("h", x=0.05, hysteresis=0.9, idle_frac=0.07,
                      restart_energy_mwh=0.4, restart_time_h=0.5)
    grid = _grid([spec])
    rep = backtest(grid, use_pallas=False)
    prices = np.asarray(grid.prices[0])
    mask = hysteresis_policy(prices, p_on=float(grid.p_on[0]),
                             p_off=float(grid.p_off[0]))
    want = float(policy_cpc(SYS, prices, mask, idle_power_frac=0.07,
                            restart_energy_mwh=0.4, restart_time_h=0.5))
    assert float(rep.cpc[0]) == pytest.approx(want, rel=1e-5)


def test_b1_always_on_matches_cpc_ao_and_oracle():
    grid = _grid([PolicySpec("ao")])
    rep = backtest(grid, use_pallas=False)
    # an always-on row realizes the AO baseline: zero reduction
    assert float(rep.cpc[0]) == pytest.approx(float(rep.cpc_ao[0]),
                                              rel=1e-6)
    assert float(rep.cpc_reduction[0]) == pytest.approx(0.0, abs=1e-6)
    # the summary's oracle column is optimal_shutdown's reduction
    summ = summarize(grid, rep)
    prices = np.asarray(grid.prices[0])
    psi = float(SYS.F) / (float(SYS.T) * float(SYS.C) * prices.mean())
    plan = optimal_shutdown(prices, psi)
    assert summ.oracle_reduction[0, 0] == pytest.approx(
        float(plan.cpc_reduction), rel=1e-5)


def test_oracle_threshold_row_attains_oracle_reduction():
    """A threshold policy at the oracle's own x_opt realizes (to within
    restart-free accounting noise) the closed-form optimum — regret ~ 0."""
    probe = _grid([PolicySpec("ao")])
    prices = np.asarray(probe.prices[0])
    psi = float(SYS.F) / (float(SYS.T) * float(SYS.C) * prices.mean())
    plan = optimal_shutdown(prices, psi)
    grid = _grid([PolicySpec("opt", x=float(plan.x_opt))])
    summ = summarize(grid, backtest(grid, use_pallas=False))
    assert abs(summ.regret[0, 0, 0]) < 1e-4


# ---------------------------------------------------------------------------
# (b) Pallas kernel vs reference scan (interpret mode on CPU)
# ---------------------------------------------------------------------------

FLEET_SCAN_CASES = [
    # N, B, T  (exercising block padding in both axes)
    (1, 1, 64),
    (2, 5, 333),
    (3, 128, 512),
    (4, 130, 1000),
]


def _params(b):
    p_off = jnp.asarray(rng.uniform(40, 160, b), jnp.float32)
    p_on = p_off * jnp.asarray(rng.uniform(0.7, 1.0, b), jnp.float32)
    lvl = jnp.asarray(rng.uniform(0.0, 0.6, b), jnp.float32)
    idle = jnp.asarray(rng.uniform(0.0, 0.3, b), jnp.float32)
    return p_on, p_off, lvl, idle


def _assert_scan_matches_ref(prices, idx, params, label, **kw):
    """The kernel, reading ``prices`` by ``idx``, against the reference
    on the gathered rows; start counts exactly."""
    got = fleet_scan(prices, idx, *params, **kw)
    want = fleet_scan_ref(jnp.asarray(prices)[jnp.asarray(idx)], *params)
    for name in want._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            rtol=2e-5, atol=1e-3, err_msg=f"{label} {name}")
    np.testing.assert_array_equal(np.asarray(got.n_starts),
                                  np.asarray(want.n_starts), err_msg=label)
    return got


@pytest.mark.parametrize("case", FLEET_SCAN_CASES)
def test_fleet_scan_matches_ref(case):
    n, b, t = case
    p = jnp.asarray(rng.normal(80, 40, (n, t)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, b), jnp.int32)
    _assert_scan_matches_ref(p, idx, _params(b), f"{n}x{b}x{t}")


@pytest.mark.parametrize("layout", ["whatif_block", "two_lane_tiles",
                                    "grouped_three_tiles", "ragged_time"])
def test_fleet_scan_reads_any_market_layout(layout):
    """Rows of mixed markets in one block (the whatif shape: 96 rows
    over 3 markets); a market axis past one 128-lane tile, rows in random
    order (sorted by market inside the wrapper) or grouped by market with
    each row block spanning several tiles (run as given, one work item a
    tile); and T not a multiple of ``block_t``."""
    n, b, t, kw = {"whatif_block": (3, 96, 200, {}),
                   "two_lane_tiles": (130, 150, 96, {}),
                   "grouped_three_tiles": (300, 200, 96, {}),
                   "ragged_time": (5, 40, 333, {"block_t": 128})}[layout]
    p = jnp.asarray(rng.normal(80, 40, (n, t)), jnp.float32)
    idx = rng.integers(0, n, b)
    if layout in ("two_lane_tiles", "grouped_three_tiles"):
        idx[:2] = (0, n - 1)             # both ends of the market axis
    if layout == "grouped_three_tiles":
        idx = np.sort(idx)
    _assert_scan_matches_ref(p, jnp.asarray(idx, jnp.int32), _params(b),
                             layout, **kw)


@pytest.mark.parametrize("markets", ["three_presets", "two_lane_tiles"])
def test_fleet_scan_row_order_is_free(markets):
    """`build_grid`'s market-major rows, and the same grid shuffled by
    `take_rows`: each row's four sums are bit-identical to its twin's,
    within one market tile and across two (where the shuffled rows take
    the wrapper's sort by market)."""
    policies = [PolicySpec("ao"), PolicySpec("x3", x=0.03),
                PolicySpec("h", x=0.05, hysteresis=0.9),
                elastic_policy("half", level=0.5, dp_total=8, x=0.05)]
    if markets == "three_presets":
        grid = _grid(policies, n_markets=3,
                     systems=(SYS, make_system(150_000.0, 1.0, float(T))))
    else:
        grid = build_grid(rng.normal(80, 40, (130, 200)),
                          [make_system(60_000.0, 1.0, 200.0)], policies[1:3])
    order = rng.permutation(grid.n_rows)

    def scan(g, label):
        return _assert_scan_matches_ref(
            g.prices, g.market_idx,
            (g.p_on, g.p_off, g.off_level, g.idle_frac), label)

    base = scan(grid, "market-major")
    shuf = scan(grid.take_rows(order), "shuffled")
    for name in base._fields:
        np.testing.assert_array_equal(np.asarray(getattr(shuf, name)),
                                      np.asarray(getattr(base, name))[order],
                                      err_msg=name)


def test_price_planes_rebuild_every_price_exactly():
    """The three bfloat16 planes are disjoint bit fields of each float32
    price: every order of summing them, and the kernel's one-hot matmul,
    gives back the price bit for bit."""
    x = np.concatenate([
        rng.normal(0, 1, 4000) * 10.0 ** rng.uniform(-20, 20, 4000),
        [0.0, 1.0, -1.0, 3.4028235e38, -1e-30, 1e-30, 8760.123]])
    x = jnp.asarray(x, jnp.float32)
    hi, mid, lo = (q.astype(jnp.float32) for q in price_planes(x))
    for total in ((hi + mid) + lo, (hi + lo) + mid, hi + (mid + lo)):
        np.testing.assert_array_equal(np.asarray(total), np.asarray(x))
    # read through a one-hot matmul: [T, 3N] planes @ a [3N, B] one-hot
    tab = jnp.reshape(x[:4000], (40, 100))               # [N, T]
    idx = jnp.asarray(rng.integers(0, 40, 64), jnp.int32)
    planes = price_planes(tab).reshape(3 * 40, 100).T     # [T, 3N]
    k = jnp.arange(3 * 40)[:, None] % 40
    onehot = (k == idx[None, :]).astype(jnp.bfloat16)
    got = jnp.dot(planes, onehot, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(tab[idx].T))


def test_fleet_scan_exact_start_count():
    """Start counting is integral — the kernel and reference must agree
    exactly, including the initial-on convention (no start at t=0)."""
    p = jnp.asarray([[50.0, 200.0, 200.0, 50.0, 200.0, 50.0, 50.0]])
    out = fleet_scan(p, jnp.asarray([0]), jnp.asarray([100.0]),
                     jnp.asarray([100.0]), jnp.asarray([0.0]),
                     jnp.asarray([0.0]))
    assert float(out.n_starts[0]) == 2.0
    assert float(out.up_units[0]) == 4.0


def test_backtest_pallas_path_matches_ref_path():
    grid = _grid([PolicySpec("ao"), PolicySpec("x3", x=0.03),
                  elastic_policy("half", level=0.5, dp_total=8, x=0.05)],
                 n_markets=2,
                 systems=(SYS, make_system(150_000.0, 1.0, float(T))))
    ref = backtest(grid, use_pallas=False)
    pal = backtest(grid, use_pallas=True)
    for f in ("cpc", "cpc_ao", "cpc_reduction", "tco", "up_hours",
              "n_starts"):
        np.testing.assert_allclose(np.asarray(getattr(ref, f)),
                                   np.asarray(getattr(pal, f)),
                                   rtol=1e-5, atol=1e-5, err_msg=f)


# First 16 hex digits of the sha256 of each `FleetReport` field on
# `_golden_grid`, as the kernel gave them when it still scanned a gathered
# [B, T] copy of the prices: reading prices by market index must not move
# a bit.
REPORT_DIGESTS = {
    "cpc": "9e1769e426253e56",
    "cpc_ao": "b34ba55bc2e20e3d",
    "cpc_reduction": "b3874848570e975e",
    "tco": "a8a1b28cc4911246",
    "energy_cost": "471aeda03108f29f",
    "restart_cost": "8f9dc91626a92e5c",
    "up_hours": "c8d317346fbbce39",
    "n_starts": "bb6374e7ee790f5d",
    "x_realized": "11ff6014109d9ff0",
    "market_idx": "1a8701019f0df8d1",
    "system_idx": "bfd323c16fbeabfd",
    "policy_idx": "42bee5b883714bc6",
}


def _golden_grid():
    """130 markets (two lane tiles) x 2 systems x 3 policies, market-major."""
    prices = np.random.default_rng(2024).normal(80, 40, (130, 300))
    return build_grid(
        prices,
        [make_system(60_000.0, 1.0, 300.0), make_system(150_000.0, 2.0, 300.0)],
        [PolicySpec("x3", x=0.03),
         PolicySpec("h", x=0.05, hysteresis=0.9, idle_frac=0.07,
                    restart_energy_mwh=0.4, restart_time_h=0.5),
         elastic_policy("half", level=0.5, dp_total=8, x=0.05)])


def test_backtest_report_bits_match_golden():
    rep = backtest(_golden_grid(), use_pallas=True)
    got = {f: hashlib.sha256(np.asarray(getattr(rep, f)).tobytes())
            .hexdigest()[:16] for f in rep._fields}
    assert got == REPORT_DIGESTS


# ---------------------------------------------------------------------------
# (c) report aggregation is permutation-invariant over rows
# ---------------------------------------------------------------------------

def test_summary_is_row_permutation_invariant():
    grid = _grid([PolicySpec("ao"), PolicySpec("x2", x=0.02),
                  PolicySpec("x5", x=0.05, hysteresis=0.9)],
                 n_markets=2,
                 systems=(SYS, make_system(150_000.0, 1.0, float(T))))
    rep = backtest(grid, use_pallas=False)
    base = summarize(grid, rep)

    order = rng.permutation(grid.n_rows)
    grid_p = grid.take_rows(order)
    rep_p = backtest(grid_p, use_pallas=False)
    perm = summarize(grid_p, rep_p)

    for field in base._fields:
        bv, pv = getattr(base, field), getattr(perm, field)
        if bv is None:        # dispatch block: absent unless configured
            assert pv is None, field
            continue
        np.testing.assert_allclose(np.asarray(bv), np.asarray(pv),
                                   rtol=1e-6, atol=1e-6, err_msg=field)


def test_grid_shapes_and_indexing():
    grid = _grid([PolicySpec("ao"), PolicySpec("x2", x=0.02)],
                 n_markets=3, systems=(SYS, SYS))
    assert grid.n_rows == 3 * 2 * 2
    assert grid.n_markets == 3 and grid.n_systems == 2
    assert grid.n_policies == 2
    # x-policies resolve per market: thresholds must differ across markets
    offs = np.asarray(grid.p_off).reshape(3, 2, 2)[:, 0, 1]
    assert len(np.unique(offs)) == 3
    # always-on rows have an infinite threshold
    assert np.all(np.isinf(np.asarray(grid.p_off).reshape(3, 2, 2)[:, :, 0]))


@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_build_grid_rejects_non_finite_prices(bad):
    """One bad hour is refused where the price matrix enters: read by
    market index, it would spoil other markets' rows too."""
    prices = rng.normal(80, 40, (3, 50))
    prices[1, 17] = bad
    with pytest.raises(ValueError, match=r"market\(s\) \[1\]"):
        build_grid(prices, [SYS], [PolicySpec("x3", x=0.03)])


def test_take_rows_carries_every_per_row_field():
    """take_rows must permute every dataclass field that is not shared —
    compared against `dataclasses.fields()` so a future per-row field
    cannot be silently dropped."""
    import dataclasses

    from repro.fleet import ScenarioGrid

    grid = _grid([PolicySpec("ao"), PolicySpec("x2", x=0.02)],
                 n_markets=2, systems=(SYS, SYS))
    order = rng.permutation(grid.n_rows)
    perm = grid.take_rows(order)
    shared = set(ScenarioGrid.SHARED_FIELDS)
    names = {f.name for f in dataclasses.fields(ScenarioGrid)}
    assert shared < names
    for f in dataclasses.fields(ScenarioGrid):
        v, pv = getattr(grid, f.name), getattr(perm, f.name)
        if f.name in shared:
            assert pv is v or np.array_equal(np.asarray(pv),
                                             np.asarray(v)), f.name
        else:
            assert v.shape[0] == grid.n_rows, \
                f"{f.name}: per-row fields must be [B]-leading"
            np.testing.assert_array_equal(
                np.asarray(v)[order], np.asarray(pv), err_msg=f.name)


def test_take_rows_refuses_non_per_row_field():
    """A field that is neither shared nor [B]-leading must raise, not be
    silently dropped."""
    import dataclasses

    grid = _grid([PolicySpec("ao")])
    bad = dataclasses.replace(grid, restart_time_h=jnp.zeros(()))
    with pytest.raises(TypeError, match="neither a shared field"):
        bad.take_rows(np.arange(grid.n_rows))


def test_policy_spec_validation():
    with pytest.raises(ValueError):
        PolicySpec("bad", x=0.1, p_off=100.0)
    with pytest.raises(ValueError):
        PolicySpec("bad", x=0.1, off_level=1.0)
    with pytest.raises(ValueError):
        # inverted band (p_on > p_off) would make kernel and reference
        # scan disagree — must be rejected at spec time
        PolicySpec("bad", x=0.1, hysteresis=1.2)
    with pytest.raises(ValueError):
        build_grid(np.zeros((2, 10), np.float32), [], [PolicySpec("ao")])


def test_summary_tolerates_partial_cube():
    """Uncovered (market, system) cells stay NaN / -1 instead of crashing
    nanargmax."""
    grid = _grid([PolicySpec("ao"), PolicySpec("x2", x=0.02)],
                 n_markets=2, systems=(SYS, SYS))
    sub = grid.take_rows(np.arange(grid.n_policies))   # market 0, sys 0 only
    summ = summarize(sub, backtest(sub, use_pallas=False))
    assert summ.best_policy[0, 0] >= 0
    assert summ.best_policy[1, 1] == -1
    assert np.isnan(summ.best_reduction[1, 1])
