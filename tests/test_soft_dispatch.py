"""Soft (differentiable) dispatch tests: the relaxation pyramid.

Layer 1 — kernel consistency: the Pallas soft-dispatch path is
bit-identical to the sequential `soft_dispatch_ref` oracle (interpret
mode), exactly like the hard `dispatch_scan`.
Layer 2 — relaxation semantics: the softmin water-fill converges to the
hard greedy fill (allocation *and* CPC) as tau -> 0, reduces to the
per-hour entropic fill with zero fee / zero dwell, and is invariant to
site permutation.
Layer 3 — gradients: reverse-mode through the water level (implicit
Newton correction) matches central finite differences in float64.
Layer 4 — the dispatch-aware tuner: fleet CPC under *hard* feasible
dispatch matches or beats the PR-3 re-score-only path on the 256-row
acceptance grid, a swing site emerges, the full pipeline is seeded-
deterministic, and chunking the coupled objective raises loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tco import make_system
from repro.dispatch import (DispatchConfig, DispatchProblem, segment_keys,
                            segment_rank, summarize_alloc)
from repro.energy.markets import MarketParams
from repro.fleet import PolicySpec, build_grid
from repro.kernels.ref import (dispatch_ref, soft_dispatch_hour,
                               soft_dispatch_ref, soft_water_level)
from repro.kernels.soft_dispatch import soft_dispatch, soft_dispatch_pallas
from repro.tune import TuneConfig, optimize

rng = np.random.default_rng(29)


def _random_case(s, t, *, demand_frac=0.5, seed_shift=0):
    r = np.random.default_rng(29 + seed_shift)
    prices = r.normal(80, 40, (s, t)).astype(np.float32)
    power = r.uniform(1.0, 3.0, s).astype(np.float32)
    on = (r.uniform(size=(s, t)) > 0.3).astype(np.float32)
    avail = power[:, None] * (0.2 + 0.8 * on)      # never fully dark
    demand = np.full(t, demand_frac * float(avail.sum(axis=0).min()),
                     np.float32)
    return prices, avail, demand


def _hard_problem(prices, avail, demand, mc, dwell):
    order, rank = segment_rank(prices, mc)
    return DispatchProblem(
        prices=np.asarray(prices, np.float32),
        avail_mw=np.asarray(avail, np.float32),
        demand_mw=np.asarray(demand, np.float32),
        power_cap_mw=float("inf"), migrate_cost=mc, min_dwell_h=dwell,
        compute_floor_mwh=0.0, fixed_cost=0.0, order=order, rank=rank)


# ---------------------------------------------------------------------------
# (a) Pallas kernel vs sequential oracle: bit-identical (interpret mode)
# ---------------------------------------------------------------------------

SOFT_CASES = [
    # S, T, migrate_cost, min_dwell, tau  (T exercising block padding)
    (1, 64, 0.0, 0, 5.0),
    (5, 333, 5.0, 0, 2.0),
    (8, 500, 5.0, 6, 0.5),
    (16, 700, 3.0, 3, 20.0),
]


@pytest.mark.parametrize("case", SOFT_CASES)
def test_soft_dispatch_pallas_bit_identical_to_ref(case):
    s, t, mc, dwell, tau = case
    prices, avail, demand = _random_case(s, t)
    keys = segment_keys(prices, mc).astype(np.float32)
    order, _ = segment_rank(prices, mc)
    got = np.asarray(soft_dispatch_pallas(avail, keys, order, demand,
                                          tau=tau, min_dwell=dwell,
                                          block_t=256))
    want = np.asarray(soft_dispatch_ref(
        jnp.asarray(avail, jnp.float32), jnp.asarray(keys), order, demand,
        tau=tau, min_dwell=dwell))
    np.testing.assert_array_equal(got, want,
                                  err_msg=f"S={s} T={t} tau={tau}")


# ---------------------------------------------------------------------------
# (b) soft -> hard convergence as tau -> 0 (allocation and CPC)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mc,dwell", [(0.0, 0), (5.0, 0), (5.0, 4)])
def test_soft_converges_to_hard_allocation(mc, dwell):
    """At tau = 1e-3 (the f32 sweet spot: smaller tau runs into f32 key
    cancellation) the relaxed allocation matches the greedy fill to
    ~1e-3 MW on O(1) MW sites."""
    prices, avail, demand = _random_case(6, 400)
    keys = segment_keys(prices, mc)
    order, rank = segment_rank(prices, mc)
    hard = np.asarray(dispatch_ref(avail, order, rank, demand,
                                   min_dwell=dwell))
    soft = np.asarray(soft_dispatch(avail, keys, order, demand,
                                    tau=1e-3, min_dwell=dwell))
    np.testing.assert_allclose(soft, hard, atol=5e-3,
                               err_msg=f"mc={mc} dwell={dwell}")


@pytest.mark.parametrize("mc,dwell", [(5.0, 4), (3.0, 8)])
def test_soft_converges_to_hard_cpc(mc, dwell):
    """CPC of the soft allocation converges to the hard CPC even in
    dwell-heavy configs where isolated lock flips can keep a few hours'
    allocations apart (the locks are hair-trigger; the cost is not)."""
    prices, avail, demand = _random_case(6, 400)
    keys = segment_keys(prices, mc)
    prob = _hard_problem(prices, avail, demand, mc, dwell)
    hard = summarize_alloc(prob, np.asarray(dispatch_ref(
        avail, prob.order, prob.rank, demand, min_dwell=dwell)))
    soft = summarize_alloc(prob, np.asarray(soft_dispatch(
        avail, keys, prob.order, demand, tau=1e-3, min_dwell=dwell)))
    assert soft.cpc == pytest.approx(hard.cpc, rel=1e-3)
    assert soft.delivered_mwh == pytest.approx(hard.delivered_mwh,
                                               rel=1e-5)


def test_temperature_monotone_smoothing():
    """Warmer temperatures spread the allocation: the max per-site
    share of a single hour's demand decreases (weakly) with tau, while
    every temperature still sums to the demand."""
    prices, avail, demand = _random_case(6, 200)
    keys = segment_keys(prices, 0.0)
    order, _ = segment_rank(prices, 0.0)
    peak = []
    for tau in (1e-2, 5.0, 50.0):
        alloc = np.asarray(soft_dispatch(avail, keys, order, demand,
                                         tau=tau))
        np.testing.assert_allclose(alloc.sum(axis=0), demand, rtol=1e-4)
        peak.append((alloc / demand).max())
    assert peak[0] >= peak[1] >= peak[2]


# ---------------------------------------------------------------------------
# (c) zero fee / zero dwell: per-hour entropic softmin fill, no recurrence
# ---------------------------------------------------------------------------

def test_zero_fee_zero_dwell_reduces_to_per_hour_softmin_fill():
    """With no migration premium and no dwell the hours decouple: the
    allocation equals the per-hour entropic water-fill over widths =
    avail at keys = prices, computed independently per hour."""
    s, t, tau = 5, 120, 3.0
    prices, avail, demand = _random_case(s, t)
    keys = segment_keys(prices, 0.0)
    order, _ = segment_rank(prices, 0.0)
    got = np.asarray(soft_dispatch(avail, keys, order, demand, tau=tau))

    inv_tau = 1.0 / tau
    for h in range(0, t, 17):
        k = prices[:, h].astype(np.float64)
        w = avail[:, h].astype(np.float64)
        o = np.argsort(k, kind="stable")
        cums = np.cumsum(w[o])
        lam0 = k[o][min(int((cums < demand[h]).sum()), s - 1)]
        lam = soft_water_level(jnp.asarray(k), jnp.asarray(w),
                               demand[h], lam0, inv_tau)
        fill = w * jax.nn.sigmoid((lam - k) * inv_tau)
        fill = fill * demand[h] / fill.sum()
        # `got` ran in f32, the recomputation here in f64: the water
        # level agrees to f32 resolution, not better
        np.testing.assert_allclose(got[:, h], np.asarray(fill),
                                   rtol=5e-4, atol=1e-4,
                                   err_msg=f"hour {h}")


def test_newton_step_survives_a_saturated_marginal_segment():
    """An hour met from one site's held load, which exceeds the demand by
    one ulp (an input that `test_soft_converges_to_hard_allocation`'s
    trajectory reaches): the level sits ~17 tau above that segment's
    key, where the f32 sigmoid rounds to 1. The Newton step's curvature
    must come from sigmoid(-z): with 1 - sigmoid(z) it is 0 there, the
    step jumps to -span and the hour allocates 0.11 MW of 1.59."""
    prices = np.array([[124.40172], [127.32521], [33.881805], [121.41237],
                       [69.988655], [146.41827]], np.float32)   # [S, 1]
    avail = np.array([0.42370734, 0.2899725, 2.974349, 1.6215409,
                      1.5403848, 0.24811159], np.float32)
    prev = np.array([0.0, 0.0, 1.5907465, 0.0, 0.0, 0.0], np.float32)
    demand = np.float32(1.5907464)
    keys = segment_keys(prices, 5.0)
    order, _ = segment_rank(prices, 5.0)
    inv_tau = jnp.float32(1e3)
    alloc, _ = soft_dispatch_hour(
        prev, np.zeros(6, np.float32), avail,
        jnp.asarray(keys[0], jnp.float32), jnp.asarray(order[0]), demand,
        inv_tau=inv_tau, inv_tau_mw=inv_tau / 0.05, min_dwell=0)
    alloc = np.asarray(alloc)
    assert alloc.sum() == pytest.approx(float(demand), rel=1e-6)
    assert alloc[2] == pytest.approx(float(demand), rel=1e-5)


def test_site_permutation_invariance():
    """Shuffling site order permutes the allocation and nothing else.

    Run without dwell locks: the fee-retention recurrence is continuous
    in the running state, so reordered f32 summation inside the water
    level stays a rounding-level effect. (The dwell counter is a
    *discrete* ledger — hair-trigger by design — so bitwise-different
    summation orders can legitimately flip a lock; its soft dynamics
    are covered by the convergence and FD tests instead.)"""
    prices, avail, demand = _random_case(9, 300)
    perm = rng.permutation(9)
    mc, tau = 6.0, 1.5

    def run(p, a):
        keys = segment_keys(p, mc)
        order, _ = segment_rank(p, mc)
        return np.asarray(soft_dispatch(a, keys, order, demand, tau=tau))

    base = run(prices, avail)
    shuf = run(prices[perm], avail[perm])
    np.testing.assert_allclose(base[perm], shuf, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# (d) gradients vs central finite differences (float64)
# ---------------------------------------------------------------------------

def test_soft_dispatch_gradients_match_fd():
    """Reverse-mode through the water level (bisection under
    stop_gradient + one differentiable Newton step) against central
    differences on availability and demand, rtol <= 1e-3 in f64."""
    with jax.enable_x64(True):
        r = np.random.default_rng(3)
        s, t = 4, 24
        prices = r.normal(80, 40, (s, t))
        avail0 = r.uniform(0.5, 2.0, (s, t))
        demand = np.full(t, 0.45 * avail0.sum(axis=0).min())
        mc = 4.0
        keys = segment_keys(prices, mc)
        order, _ = segment_rank(prices, mc)

        def cost(avail, dem):
            alloc = soft_dispatch_ref(avail, keys, order, dem, tau=3.0,
                                      min_dwell=3)
            return jnp.sum(alloc * jnp.asarray(prices))

        g_a = jax.grad(cost, argnums=0)(jnp.asarray(avail0),
                                        jnp.asarray(demand))
        g_d = jax.grad(cost, argnums=1)(jnp.asarray(avail0),
                                        jnp.asarray(demand))
        for i, j in zip(r.integers(0, s, 8), r.integers(0, t, 8)):
            h = 1e-6
            hi, lo = avail0.copy(), avail0.copy()
            hi[i, j] += h
            lo[i, j] -= h
            fd = (float(cost(jnp.asarray(hi), jnp.asarray(demand)))
                  - float(cost(jnp.asarray(lo), jnp.asarray(demand)))
                  ) / (2 * h)
            np.testing.assert_allclose(
                float(g_a[i, j]), fd, rtol=1e-3, atol=1e-4,
                err_msg=f"d/d avail[{i},{j}]")
        for j in (0, 7, 23):
            h = 1e-6
            hi, lo = demand.copy(), demand.copy()
            hi[j] += h
            lo[j] -= h
            fd = (float(cost(jnp.asarray(avail0), jnp.asarray(hi)))
                  - float(cost(jnp.asarray(avail0), jnp.asarray(lo)))
                  ) / (2 * h)
            np.testing.assert_allclose(float(g_d[j]), fd, rtol=1e-3,
                                       atol=1e-4,
                                       err_msg=f"d/d demand[{j}]")


def test_dispatch_aware_objective_gradients_match_fd():
    """Central FD through the *whole* dispatch-aware soft objective —
    scan relaxation, soft selection, water-fill, migration accounting —
    on every raw coordinate, rtol <= 1e-3 in f64. Uses the same FD
    harness the CI benchmark gate runs (`benchmarks.bench_tune.
    fd_grad_worst_rel_err`), at a different horizon so the two probe
    different fixed-seed problems."""
    from benchmarks.bench_tune import fd_grad_worst_rel_err
    worst = fd_grad_worst_rel_err(t=72)
    assert worst <= 1e-3, f"worst FD-vs-autodiff rel err {worst:.2e}"


# ---------------------------------------------------------------------------
# (e) dispatch-aware tuning: acceptance, swing site, determinism, chunking
# ---------------------------------------------------------------------------

_T = 400
_DCFG = DispatchConfig(demand_frac=0.25, migrate_cost=4.0, min_dwell_h=3)


def _fleet_grid(n_markets=3, n_policies=3, t=_T):
    markets = [MarketParams(n_hours=t, seed=s) for s in range(n_markets)]
    sys = make_system(0.5 * t * 80.0, 1.0, float(t))
    pols = [PolicySpec("ao"), PolicySpec("x5", x=0.05, off_level=0.3),
            PolicySpec("x10", x=0.10, off_level=0.3)][:n_policies]
    return build_grid(markets, [sys], pols)


def _acceptance_grid():
    """The fixed-seed 256-row grid of tests/test_tune.py, with a partial
    off-level so shut sites still offer dispatchable capacity."""
    t = 600
    markets = [MarketParams(n_hours=t, seed=s) for s in range(4)]
    systems = [make_system(float(psi) * t * 1.0 * 80.0, 1.0, float(t))
               for psi in (0.5, 1.0, 2.0, 4.0)]
    xs = (0.01, 0.02, 0.03, 0.05, 0.08, 0.10, 0.12, 0.15,
          0.20, 0.25, 0.30, 0.40)
    policies = [PolicySpec("ao")] + \
        [PolicySpec(f"x{int(x * 100)}", x=x, off_level=0.25)
         for x in xs] + \
        [PolicySpec("x3h", x=0.03, hysteresis=0.9, off_level=0.25),
         PolicySpec("x8h", x=0.08, hysteresis=0.85, off_level=0.25),
         PolicySpec("x15h", x=0.15, hysteresis=0.9, off_level=0.25)]
    return build_grid(markets, systems, policies)


def test_dispatch_aware_beats_rescore_only_on_acceptance_grid():
    """The tentpole acceptance: on the 256-row grid, dispatch-aware
    tuned policies hard-re-scored on feasible `dispatch()` achieve
    fleet CPC <= the PR-3 re-score-only path, and never worse than the
    best-swept set (min(tuned, swept) is reported either way)."""
    grid = _acceptance_grid()
    assert grid.n_rows == 256
    dcfg = DispatchConfig(demand_frac=0.3, migrate_cost=4.0,
                          min_dwell_h=3)
    rescore = optimize(grid, TuneConfig(steps=150, dispatch=dcfg))
    aware = optimize(grid, TuneConfig(steps=150, dispatch_soft=dcfg))
    cpc_rescore = min(rescore.dispatch["cpc_tuned"],
                      rescore.dispatch["cpc_swept"])
    cpc_aware = min(aware.dispatch["cpc_tuned"],
                    aware.dispatch["cpc_swept"])
    assert np.isfinite(cpc_aware)
    assert cpc_aware <= cpc_rescore * (1.0 + 1e-9)
    # the guarantee survives the coupling: never worse than best swept
    assert cpc_aware <= aware.dispatch["cpc_swept"] * (1.0 + 1e-9)


def test_swing_site_effect():
    """Under the fleet objective at least one site learns a materially
    different threshold than isolated tuning: with spare fleet capacity
    some candidate is pushed toward an always-on backup role (threshold
    far above the isolated optimum) so cheaper sites can chase prices."""
    grid = _fleet_grid()
    iso = optimize(grid, TuneConfig(steps=60, dispatch=_DCFG))
    aware = optimize(grid, TuneConfig(steps=60, dispatch_soft=_DCFG))
    p_iso = np.asarray(iso.params.p_off)
    p_aware = np.asarray(aware.params.p_off)
    # materially different: at least one site moved its shutdown
    # threshold by more than 20% of the isolated value
    rel = np.abs(p_aware - p_iso) / np.abs(p_iso)
    assert rel.max() > 0.2, (p_iso, p_aware)
    # and the role-shaped fleet is at least as good under *hard*
    # feasible dispatch (the dispatch_ratio history itself is measured
    # at the annealing τ of its step, so its endpoints are not
    # comparable — the hard re-score is)
    cpc_iso = min(iso.dispatch["cpc_tuned"], iso.dispatch["cpc_swept"])
    cpc_aware = min(aware.dispatch["cpc_tuned"],
                    aware.dispatch["cpc_swept"])
    assert np.isfinite(cpc_aware)
    assert cpc_aware <= cpc_iso * (1.0 + 1e-9)


def test_dispatch_aware_pipeline_seeded_determinism():
    """Full pipeline (build_grid -> tune_loop(dispatch_soft) -> hard
    dispatch re-score) twice from the same seed is bit-identical."""
    def run():
        grid = _fleet_grid()
        res = optimize(grid, TuneConfig(steps=25, dispatch_soft=_DCFG))
        return res

    a, b = run(), run()
    for field in ("p_on", "p_off", "off_level"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.params, field)),
            np.asarray(getattr(b.params, field)), err_msg=field)
    np.testing.assert_array_equal(a.cpc, b.cpc)
    assert a.dispatch["cpc_tuned"] == b.dispatch["cpc_tuned"]
    assert a.dispatch["cpc_swept"] == b.dispatch["cpc_swept"]
    if a.dispatch["tuned"] is not None:
        np.testing.assert_array_equal(a.dispatch["tuned"].alloc_mw,
                                      b.dispatch["tuned"].alloc_mw)


def test_chunked_dispatch_aware_objective_raises():
    """Coupled rows cannot chunk: the water level spans the whole
    fleet, so `chunk_rows` with `dispatch_soft` must raise instead of
    silently optimizing a different objective."""
    grid = _fleet_grid()
    with pytest.raises(ValueError, match="dispatch_soft"):
        optimize(grid, TuneConfig(steps=5, chunk_rows=4,
                                  dispatch_soft=_DCFG))


def test_dispatch_reeval_runs_under_dispatch_soft_alone():
    """dispatch_soft alone (no TuneConfig.dispatch) still hard-scores
    the final sets on feasible dispatch()."""
    grid = _fleet_grid()
    res = optimize(grid, TuneConfig(steps=20, dispatch_soft=_DCFG))
    d = res.dispatch
    assert d is not None and d["chosen"] in ("tuned", "swept")
    chosen = d[d["chosen"]]
    demand = _DCFG.demand_frac * grid.n_markets * 1.0
    np.testing.assert_allclose(chosen.alloc_mw.sum(axis=0),
                               np.full(_T, demand), rtol=1e-4)
