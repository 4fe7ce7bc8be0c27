"""Cross-site dispatch subsystem tests: Pallas kernel vs sequential
oracle (bit-identical), site-permutation invariance, hard-constraint
feasibility at the extremes, schedule consistency with the fleet scan,
the `summarize` round-trip with the dispatch block, [T] demand
profiles end to end, and property-based invariants of the hard
water-fill over random feasible problems."""

import numpy as np
import pytest

from repro.core.tco import make_system
from repro.dispatch import (DispatchConfig, DispatchInfeasible,
                            DispatchProblem, build_problem,
                            capacity_series, dispatch, diurnal_demand,
                            resolve_demand, segment_keys, segment_rank)
from repro.energy.markets import MarketParams
from repro.fleet import PolicySpec, backtest, build_grid, summarize
from repro.kernels.dispatch_scan import dispatch_scan, time_block
from repro.kernels.ref import dispatch_ref, fleet_scan_ref

from tests._hypothesis_compat import given, settings, st

rng = np.random.default_rng(17)


def _random_case(s, t, *, demand_frac=0.5, seed_shift=0):
    """Random prices/availability with a feasible constant demand."""
    r = np.random.default_rng(17 + seed_shift)
    prices = r.normal(80, 40, (s, t)).astype(np.float32)
    power = r.uniform(1.0, 3.0, s).astype(np.float32)
    on = (r.uniform(size=(s, t)) > 0.3).astype(np.float32)
    avail = power[:, None] * (0.2 + 0.8 * on)      # never fully dark
    demand = np.full(t, demand_frac * float(avail.sum(axis=0).min()),
                     np.float32)
    return prices, avail, demand


def _problem(prices, avail, demand, *, migrate_cost=0.0, min_dwell=0,
             power_cap=float("inf"), floor=0.0, fixed=0.0):
    order, rank = segment_rank(prices, migrate_cost)
    return DispatchProblem(
        prices=np.asarray(prices, np.float32),
        avail_mw=np.asarray(avail, np.float32),
        demand_mw=np.asarray(demand, np.float32),
        power_cap_mw=power_cap, migrate_cost=migrate_cost,
        min_dwell_h=min_dwell, compute_floor_mwh=floor, fixed_cost=fixed,
        order=order, rank=rank)


# ---------------------------------------------------------------------------
# (a) Pallas kernel vs sequential oracle: bit-identical
# ---------------------------------------------------------------------------

DISPATCH_CASES = [
    # S, T, migrate_cost, min_dwell  (T exercising block padding)
    (1, 64, 0.0, 0),
    (5, 333, 5.0, 0),
    (16, 1000, 5.0, 6),
    (64, 700, 0.0, 3),
]


@pytest.mark.parametrize("case", DISPATCH_CASES)
def test_dispatch_scan_bit_identical_to_ref(case):
    s, t, mc, dwell = case
    prices, avail, demand = _random_case(s, t)
    order, rank = segment_rank(prices, mc)
    got = np.asarray(dispatch_scan(avail, order, rank, demand,
                                   min_dwell=dwell, block_t=256))
    want = np.asarray(dispatch_ref(avail, order, rank, demand,
                                   min_dwell=dwell))
    np.testing.assert_array_equal(got, want,
                                  err_msg=f"S={s} T={t} mc={mc}")


def test_dispatch_scan_rounds_block_t_to_the_sublane_tile():
    """A block of 100 hours is not a multiple of 8, which the TPU tiling
    refuses; the wrapper runs 96-hour blocks instead, with the same
    allocation."""
    assert time_block(1000, 100) == 96 and time_block(300, 512) == 300
    prices, avail, demand = _random_case(6, 1000)
    order, rank = segment_rank(prices, 5.0)
    got = np.asarray(dispatch_scan(avail, order, rank, demand,
                                   min_dwell=2, block_t=100))
    want = np.asarray(dispatch_ref(avail, order, rank, demand,
                                   min_dwell=2))
    np.testing.assert_array_equal(got, want)


def test_dispatch_engine_paths_identical():
    prices, avail, demand = _random_case(7, 500)
    prob = _problem(prices, avail, demand, migrate_cost=3.0, min_dwell=4)
    ref = dispatch(prob, use_pallas=False)
    pal = dispatch(prob, use_pallas=True)
    np.testing.assert_array_equal(ref.alloc_mw, pal.alloc_mw)
    assert ref.cpc == pal.cpc and ref.n_migrations == pal.n_migrations


# ---------------------------------------------------------------------------
# (b) allocation semantics
# ---------------------------------------------------------------------------

def test_demand_is_met_exactly_within_availability():
    prices, avail, demand = _random_case(9, 400)
    res = dispatch(_problem(prices, avail, demand, migrate_cost=4.0,
                            min_dwell=5), use_pallas=False)
    np.testing.assert_allclose(res.alloc_mw.sum(axis=0), demand,
                               rtol=1e-5, atol=1e-4)
    assert np.all(res.alloc_mw <= np.asarray(avail) + 1e-5)
    assert np.all(res.alloc_mw >= 0.0)


def test_zero_migration_cost_reduces_to_per_hour_argmin():
    """With no fee and no dwell the dispatcher fills the cheapest
    available sites each hour independently (greedy price argmin)."""
    s, t = 6, 200
    prices, avail, demand = _random_case(s, t)
    res = dispatch(_problem(prices, avail, demand), use_pallas=False)
    want = np.zeros((s, t))
    for h in range(t):
        left = float(demand[h])
        for i in np.argsort(prices[:, h], kind="stable"):
            take = min(left, float(avail[i, h]))
            want[i, h] = take
            left -= take
    np.testing.assert_allclose(res.alloc_mw, want, rtol=1e-5, atol=1e-4)


def test_site_permutation_invariance():
    """Permuting site order permutes the allocation rows and nothing
    else (prices are continuous draws, so sort keys are distinct)."""
    prices, avail, demand = _random_case(11, 300)
    perm = rng.permutation(11)
    base = dispatch(_problem(prices, avail, demand, migrate_cost=6.0,
                             min_dwell=3), use_pallas=False)
    shuf = dispatch(_problem(prices[perm], avail[perm], demand,
                             migrate_cost=6.0, min_dwell=3),
                    use_pallas=False)
    np.testing.assert_array_equal(base.alloc_mw[perm], shuf.alloc_mw)
    assert base.cpc == pytest.approx(shuf.cpc, rel=1e-12)
    assert base.n_migrations == shuf.n_migrations
    assert base.migration_mw == pytest.approx(shuf.migration_mw,
                                              rel=1e-9, abs=1e-9)


def test_migration_fee_and_dwell_suppress_thrash():
    """More friction, fewer moves — and hour 0's initial placement is
    never billed as migration."""
    prices, avail, demand = _random_case(8, 600)
    free = dispatch(_problem(prices, avail, demand), use_pallas=False)
    fee = dispatch(_problem(prices, avail, demand, migrate_cost=15.0),
                   use_pallas=False)
    dwell = dispatch(_problem(prices, avail, demand, migrate_cost=15.0,
                              min_dwell=12), use_pallas=False)
    assert free.n_migrations > fee.n_migrations >= dwell.n_migrations
    assert free.migration_cost == 0.0          # no fee, no bill
    assert fee.migration_cost > 0.0
    # the free allocation chases prices: it pays the least for energy
    assert free.energy_cost <= fee.energy_cost + 1e-6
    assert free.energy_cost <= dwell.energy_cost + 1e-6


def test_min_dwell_holds_load_in_place():
    """Two sites, prices flipping every hour: without dwell the load
    hops every hour; with min_dwell=4 it moves at most every 4th hour
    (capacity stays ample, so locks are never force-broken)."""
    t = 96
    flip = np.tile([1.0, 0.0], t // 2)
    prices = np.stack([40.0 + 30.0 * flip, 40.0 + 30.0 * (1 - flip)]) \
        .astype(np.float32)
    avail = np.full((2, t), 2.0, np.float32)
    demand = np.full(t, 1.5, np.float32)
    hop = dispatch(_problem(prices, avail, demand), use_pallas=False)
    held = dispatch(_problem(prices, avail, demand, migrate_cost=1e-3,
                             min_dwell=4), use_pallas=False)
    assert hop.n_migrations == t - 1
    assert held.n_migrations <= (t - 1) // 4 + 1
    moves = np.abs(np.diff(held.alloc_mw, axis=1)).sum(axis=0)
    move_hours = np.flatnonzero(moves > 1e-6)
    assert np.all(np.diff(move_hours) >= 4)


# ---------------------------------------------------------------------------
# (b2) [T] demand profiles end to end
# ---------------------------------------------------------------------------

def test_demand_profile_is_followed_hour_by_hour():
    s, t = 5, 240
    prices, avail, _ = _random_case(s, t)
    base = 0.4 * float(avail.sum(axis=0).min())
    profile = np.asarray(diurnal_demand(t, base_mw=base,
                                        swing_mw=0.5 * base),
                         np.float32)
    assert profile.min() > 0.0 and profile.max() <= avail.sum(axis=0).min()
    res = dispatch(_problem(prices, avail, profile, migrate_cost=2.0),
                   use_pallas=False)
    np.testing.assert_allclose(res.alloc_mw.sum(axis=0), profile,
                               rtol=1e-4, atol=1e-4)
    # ramps are demand changes, not migrations: the billed volume is
    # the matched in/out flow, strictly below the total |delta| the
    # hourly ramps produce
    delta = np.abs(np.diff(res.alloc_mw, axis=1)).sum()
    assert 0.0 < res.migration_mw < delta


def test_dispatch_config_profile_through_build_problem():
    t = 96
    grid_prices = rng.normal(80, 30, (3, t)).astype(np.float32)
    prof = diurnal_demand(t, base_mw=1.0, swing_mw=0.4)
    cfg = DispatchConfig(demand_mw=prof, migrate_cost=1.0)
    prob = build_problem(grid_prices, np.full(3, 60.0), np.full(3, 70.0),
                         np.full(3, 0.5), np.full(3, 1.0), cfg)
    np.testing.assert_allclose(prob.demand_mw, np.asarray(prof),
                               rtol=1e-6)
    assert isinstance(hash(cfg), int)   # tuple profile stays hashable


def test_demand_profile_wrong_length_raises():
    cfg = DispatchConfig(demand_mw=tuple(np.ones(50)))
    with pytest.raises(ValueError, match="50 entries"):
        resolve_demand(cfg, np.ones(3), 96)
    with pytest.raises(ValueError, match="swing_mw"):
        diurnal_demand(24, base_mw=1.0, swing_mw=2.0)


def test_summarize_dispatch_with_diurnal_profile():
    grid = _fleet_grid()
    rep = backtest(grid, use_pallas=False)
    # peak must clear the worst-case fleet hour (all three best-policy
    # sites at off_level 0.3 -> 0.9 MW): peak 0.84 MW stays feasible
    prof = diurnal_demand(T, base_mw=0.2 * grid.n_markets,
                          swing_mw=0.08 * grid.n_markets)
    summ = summarize(grid, rep, dispatch_cfg=DispatchConfig(
        demand_mw=prof, migrate_cost=4.0, min_dwell_h=3))
    d = summ.dispatch
    np.testing.assert_allclose(d.alloc_mw.sum(axis=0), np.asarray(prof),
                               rtol=1e-4)
    assert summ.dispatch_rows is not None
    assert len(summ.dispatch_rows) == grid.n_markets


# ---------------------------------------------------------------------------
# (b3) property-based invariants of the hard water-fill
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None, derandomize=True)
@given(seed=st.integers(0, 10_000),
       s=st.integers(1, 12),
       mc=st.floats(0.0, 30.0),
       dwell=st.integers(0, 10),
       frac=st.floats(0.05, 0.95))
def test_dispatch_invariants_on_random_feasible_problems(
        seed, s, mc, dwell, frac):
    """For any feasible problem: allocations meet demand exactly, never
    exceed availability, stay non-negative, and dwell-locked load is
    held (a site that just gained load does not shed it within the
    lock, capacity permitting)."""
    t = 150
    prices, avail, demand = _random_case(s, t, demand_frac=frac,
                                         seed_shift=seed)
    res = dispatch(_problem(prices, avail, demand, migrate_cost=mc,
                            min_dwell=dwell), use_pallas=False)
    alloc = res.alloc_mw
    np.testing.assert_allclose(alloc.sum(axis=0), demand, rtol=1e-4,
                               atol=1e-4)
    assert np.all(alloc <= np.asarray(avail, np.float64) + 1e-4)
    assert np.all(alloc >= 0.0)
    if dwell > 0:
        # replay the lock ledger: after an allocation *increase* a
        # site's load may not drop for `dwell` hours unless its own
        # availability drops below the held level (physics beats
        # contract) or the fleet demand sinks below the sum of locks
        ledger = np.zeros(s)
        prev = np.zeros(s)
        for h in range(t):
            locked = ledger > 0
            can_hold = np.minimum(prev, avail[:, h])
            if demand[h] >= can_hold[locked].sum() - 1e-4:
                assert np.all(alloc[:, h][locked]
                              >= can_hold[locked] - 1e-3), f"hour {h}"
            gained = alloc[:, h] > prev + 1e-3
            ledger = np.where(gained, dwell, np.maximum(ledger - 1, 0))
            prev = alloc[:, h]


@settings(max_examples=20, deadline=None, derandomize=True)
@given(seed=st.integers(0, 10_000), s=st.integers(2, 10),
       mc=st.floats(0.5, 25.0))
def test_dispatch_total_cost_monotone_in_migrate_cost(seed, s, mc):
    """The zero-fee dispatch is the per-hour cost optimum, so any
    positive fee can only cost more in total (energy + migration); and
    along an increasing fee ladder the *billed migration volume* never
    grows (more friction, fewer MW moved)."""
    t = 200
    prices, avail, demand = _random_case(s, t, seed_shift=seed)
    free = dispatch(_problem(prices, avail, demand), use_pallas=False)
    free_total = free.energy_cost       # no fee -> no migration bill
    moved_prev = free.migration_mw
    for fee in (0.5 * mc, mc, 2.0 * mc):
        res = dispatch(_problem(prices, avail, demand, migrate_cost=fee),
                       use_pallas=False)
        total = res.energy_cost + res.migration_cost
        assert total >= free_total - 1e-6 * max(1.0, abs(free_total))
        assert res.migration_mw <= moved_prev * (1.0 + 1e-6) + 1e-6
        moved_prev = res.migration_mw


# ---------------------------------------------------------------------------
# (c) hard constraints: loud infeasibility + reported slack
# ---------------------------------------------------------------------------

def test_power_cap_below_demand_raises():
    prices, avail, demand = _random_case(4, 100)
    with pytest.raises(DispatchInfeasible, match="power cap"):
        dispatch(_problem(prices, avail, demand,
                          power_cap=float(demand.min()) * 0.5))


def test_availability_shortfall_raises():
    prices, avail, demand = _random_case(4, 100)
    short = avail.copy()
    short[:, 42] = 0.0                 # one dark hour sinks the fleet
    with pytest.raises(DispatchInfeasible, match="worst hour 42"):
        dispatch(_problem(prices, short, demand))


def test_compute_floor_above_demand_raises():
    prices, avail, demand = _random_case(4, 100)
    with pytest.raises(DispatchInfeasible, match="compute floor"):
        dispatch(_problem(prices, avail, demand,
                          floor=float(demand.sum()) * 1.5))


def test_feasible_slack_is_reported():
    prices, avail, demand = _random_case(5, 200)
    cap = float(demand.max()) + 7.0
    res = dispatch(_problem(prices, avail, demand, power_cap=cap,
                            floor=float(demand.sum()) * 0.5),
                   use_pallas=False)
    assert res.slack_power_mw == pytest.approx(7.0, abs=1e-4)
    want_cap_slack = float((avail.sum(axis=0) - demand).min())
    assert res.slack_capacity_mw == pytest.approx(want_cap_slack,
                                                  rel=1e-5)
    assert res.slack_floor_mwh == pytest.approx(
        res.delivered_mwh - float(demand.sum()) * 0.5, rel=1e-6)


# ---------------------------------------------------------------------------
# (d) schedules match the fleet scan's state machine
# ---------------------------------------------------------------------------

def test_capacity_series_consistent_with_fleet_scan():
    s, t = 6, 500
    prices = rng.normal(80, 40, (s, t)).astype(np.float32)
    p_off = rng.uniform(40, 160, s).astype(np.float32)
    p_on = p_off * rng.uniform(0.7, 1.0, s).astype(np.float32)
    lvl = rng.uniform(0.0, 0.6, s).astype(np.float32)
    cap = np.asarray(capacity_series(prices, p_on, p_off, lvl))
    scan = fleet_scan_ref(prices, p_on, p_off, lvl, np.zeros(s))
    np.testing.assert_allclose(cap.sum(axis=1), np.asarray(scan.up_units),
                               rtol=1e-5, atol=1e-2)
    assert np.all((cap >= lvl[:, None] - 1e-6) & (cap <= 1.0))


# ---------------------------------------------------------------------------
# (e) summarize round-trip with the dispatch block
# ---------------------------------------------------------------------------

T = 400
SYS = make_system(fixed=0.5 * T * 80.0, power=1.0, period=float(T))
CFG = DispatchConfig(demand_frac=0.3, migrate_cost=4.0, min_dwell_h=3)


def _fleet_grid(n_markets=3):
    markets = [MarketParams(n_hours=T, seed=s) for s in range(n_markets)]
    return build_grid(markets, [SYS],
                      [PolicySpec("ao"),
                       PolicySpec("x5", x=0.05, off_level=0.3),
                       PolicySpec("x10", x=0.10, off_level=0.3)])


def test_summarize_dispatch_block_round_trip():
    grid = _fleet_grid()
    rep = backtest(grid, use_pallas=False)
    summ = summarize(grid, rep, dispatch_cfg=CFG)
    d = summ.dispatch
    assert d is not None
    assert d.alloc_mw.shape == (grid.n_markets, T)
    demand = CFG.demand_frac * grid.n_markets * float(SYS.C)
    np.testing.assert_allclose(d.alloc_mw.sum(axis=0),
                               np.full(T, demand), rtol=1e-4)
    assert d.delivered_mwh == pytest.approx(demand * T, rel=1e-5)
    # CPC folds fixed + energy + migration over delivered compute
    assert d.cpc == pytest.approx(
        (grid.n_markets * float(SYS.F) + d.energy_cost
         + d.migration_cost) / d.delivered_mwh, rel=1e-9)
    # without a config the block is absent
    assert summarize(grid, rep).dispatch is None


def test_summarize_dispatch_block_permutation_invariant():
    grid = _fleet_grid()
    rep = backtest(grid, use_pallas=False)
    base = summarize(grid, rep, dispatch_cfg=CFG).dispatch
    order = rng.permutation(grid.n_rows)
    grid_p = grid.take_rows(order)
    perm = summarize(grid_p, backtest(grid_p, use_pallas=False),
                     dispatch_cfg=CFG).dispatch
    for field in base._fields:
        np.testing.assert_allclose(np.asarray(getattr(base, field)),
                                   np.asarray(getattr(perm, field)),
                                   rtol=1e-6, atol=1e-6, err_msg=field)


def test_summarize_dispatch_infeasible_raises():
    grid = _fleet_grid()
    rep = backtest(grid, use_pallas=False)
    bad = CFG._replace(power_cap_mw=0.1)
    with pytest.raises(DispatchInfeasible):
        summarize(grid, rep, dispatch_cfg=bad)


def test_tune_dispatch_reeval():
    """TuneConfig.dispatch re-scores tuned vs swept policy sets on
    feasible dispatch and reports both."""
    from repro.tune import TuneConfig, optimize
    grid = _fleet_grid()
    res = optimize(grid, TuneConfig(steps=20, dispatch=CFG))
    d = res.dispatch
    assert d is not None and d["chosen"] in ("tuned", "swept")
    chosen = d[d["chosen"]]
    assert chosen is not None
    assert min(d["cpc_tuned"], d["cpc_swept"]) == pytest.approx(
        chosen.cpc, rel=1e-12)
    # feasible by construction: per-hour demand met by the chosen set
    demand = CFG.demand_frac * grid.n_markets * float(SYS.C)
    np.testing.assert_allclose(chosen.alloc_mw.sum(axis=0),
                               np.full(T, demand), rtol=1e-4)
