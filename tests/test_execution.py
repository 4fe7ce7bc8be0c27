"""Execution-plan / coupling API tests plus the coupled-sharded psum
acceptance and the fused soft-dispatch VJP pyramid.

Layer 1 — the `repro.execution` pair: `ExecutionPlan` / `Coupling`
constructor invariants, the chunk-under-coupling legality rule, and the
one generic `take_rows` behind `ScenarioGrid`, `LiveGrid` and the
tuner's problem slicing.
Layer 2 — deprecation shims: the pre-redesign `TuneConfig` /
`backtest(chunk_rows=)` spellings warn, forward, and produce identical
results; mixing old and new raises.
Layer 3 — the fused soft-dispatch VJP: values bitwise against
`soft_dispatch_ref`, gradients against the sequential
`soft_dispatch_grad_ref` oracle and native autodiff (f64 under the CI
x64 leg), odd-T padded blocks, and interpret-mode Pallas parity.
Layer 4 — sharded-but-coupled: on >= 2 devices the psum-reduced
coupled objective matches the single program's loss to ULP on the
256-row acceptance grid, its gradient survives an f64 FD check, and a
warm start is carried through the sharded path's row padding instead
of being silently ignored.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tco import make_system
from repro.dispatch import (DispatchConfig, build_problem, dispatch,
                            segment_keys, segment_rank)
from repro.energy.markets import MarketParams
from repro.execution import (Coupling, ExecutionPlan, take_rows,
                             validate_plan_coupling)
from repro.fleet import PolicySpec, backtest, build_grid
from repro.kernels.ref import soft_dispatch_grad_ref, soft_dispatch_ref
from repro.kernels.soft_dispatch import (soft_dispatch,
                                         soft_dispatch_fused)
from repro.live.grid import build_live_grid
from repro.tune import (TuneConfig, dispatch_coupling_from_grid,
                        init_from_grid, optimize, problem_from_grid,
                        sharded_soft_objective, soft_objective)

F64 = jax.config.jax_enable_x64
N_DEV = len(jax.devices())

_DCFG = DispatchConfig(demand_frac=0.25, migrate_cost=4.0, min_dwell_h=2)


def _grid(n_markets=2, n_policies=4, t=300, off_level=0.3):
    markets = [MarketParams(n_hours=t, seed=s) for s in range(n_markets)]
    sys = make_system(0.6 * t * 80.0, 1.0, float(t))
    pols = [PolicySpec("ao")] + [
        PolicySpec(f"x{i}", x=0.03 * (i + 1), off_level=off_level)
        for i in range(n_policies - 1)]
    return build_grid(markets, [sys], pols)


# ---------------------------------------------------------------------------
# (1) ExecutionPlan / Coupling invariants
# ---------------------------------------------------------------------------

def test_execution_plan_invariants():
    ExecutionPlan()                                   # auto is fine
    ExecutionPlan(mode="chunked", chunk_rows=2)
    ExecutionPlan(mode="sharded", devices=4)
    with pytest.raises(ValueError, match="mode"):
        ExecutionPlan(mode="turbo")
    with pytest.raises(ValueError, match="chunk_rows must be >= 2"):
        ExecutionPlan(chunk_rows=1)
    with pytest.raises(ValueError, match="needs"):
        ExecutionPlan(mode="chunked")
    with pytest.raises(ValueError, match="does not chunk"):
        ExecutionPlan(mode="sharded", chunk_rows=4)
    with pytest.raises(ValueError, match="ULP"):
        ExecutionPlan(mode="sharded", contract="bitwise")


def test_coupling_binds_semantics():
    assert not Coupling().binds
    # reeval alone is post-hoc scoring, not a coupled term
    assert not Coupling(reeval=_DCFG).binds
    assert Coupling(power_cap_mw=10.0).binds
    assert Coupling(dispatch=_DCFG).binds
    assert Coupling(reeval=_DCFG).reeval_config is _DCFG
    assert Coupling(dispatch=_DCFG).reeval_config is _DCFG


def test_chunk_under_coupling_is_constructor_invariant():
    plan = ExecutionPlan(mode="chunked", chunk_rows=4)
    validate_plan_coupling(plan, Coupling())          # unbound: fine
    with pytest.raises(ValueError, match="sharded"):
        validate_plan_coupling(plan, Coupling(dispatch=_DCFG))
    # and the same rule fires at TuneConfig assembly, old or new style
    with pytest.raises(ValueError, match="dispatch_soft"):
        TuneConfig(plan=plan, coupling=Coupling(dispatch=_DCFG))


# ---------------------------------------------------------------------------
# (2) deprecation shims
# ---------------------------------------------------------------------------

def test_tuneconfig_old_spellings_warn_and_forward():
    with pytest.deprecated_call():
        cfg = TuneConfig(chunk_rows=8)
    assert cfg.resolved_plan == ExecutionPlan(
        mode="chunked", chunk_rows=8, contract="bitwise")
    with pytest.deprecated_call():
        cfg = TuneConfig(shard=False)
    assert cfg.resolved_plan.mode == "single"
    with pytest.deprecated_call():
        cfg = TuneConfig(power_cap_mw=5.0, dispatch_soft=_DCFG)
    rc = cfg.resolved_coupling
    assert rc.power_cap_mw == 5.0 and rc.dispatch is _DCFG and rc.binds
    with pytest.deprecated_call():
        cfg = TuneConfig(dispatch=_DCFG)      # reeval-only: not bound
    assert not cfg.resolved_coupling.binds
    assert cfg.resolved_coupling.reeval_config is _DCFG


def test_tuneconfig_new_spellings_do_not_warn():
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = TuneConfig(plan=ExecutionPlan(mode="single"),
                         coupling=Coupling(dispatch=_DCFG))
    assert cfg.resolved_plan.mode == "single"
    assert cfg.resolved_coupling.binds


def test_tuneconfig_mixing_old_and_new_raises():
    with pytest.raises(ValueError, match="not both"):
        TuneConfig(chunk_rows=4, plan=ExecutionPlan())
    with pytest.raises(ValueError, match="not both"):
        TuneConfig(dispatch_soft=_DCFG, coupling=Coupling())


def test_backtest_chunk_rows_deprecated_but_identical():
    grid = _grid()
    ref = backtest(grid, use_pallas=False)
    with pytest.deprecated_call():
        old = backtest(grid, use_pallas=False, chunk_rows=3)
    new = backtest(grid, use_pallas=False,
                   plan=ExecutionPlan(mode="chunked", chunk_rows=3,
                                      contract="bitwise"))
    np.testing.assert_array_equal(np.asarray(old.cpc),
                                  np.asarray(new.cpc))
    np.testing.assert_array_equal(np.asarray(ref.cpc),
                                  np.asarray(new.cpc))
    with pytest.raises(ValueError, match="not both"):
        backtest(grid, chunk_rows=3, plan=ExecutionPlan())
    with pytest.raises(ValueError, match="does not shard"):
        backtest(grid, plan=ExecutionPlan(mode="sharded"))


def test_dispatch_plan_modes():
    prices = np.asarray(
        60 + 25 * np.random.RandomState(0).randn(4, 96), np.float64)
    problem = build_problem(prices, np.full(4, 300.0), np.full(4, 250.0),
                            np.zeros(4), np.ones(4), _DCFG)
    ref = dispatch(problem, use_pallas=False)
    single = dispatch(problem, plan=ExecutionPlan(mode="single"))
    np.testing.assert_array_equal(ref.alloc_mw, single.alloc_mw)
    for mode in ("chunked", "sharded"):
        plan = ExecutionPlan(mode=mode, chunk_rows=2) \
            if mode == "chunked" else ExecutionPlan(mode=mode)
        with pytest.raises(ValueError, match="no row axis"):
            dispatch(problem, plan=plan)


# ---------------------------------------------------------------------------
# (3) the one generic take_rows
# ---------------------------------------------------------------------------

def test_generic_take_rows_matches_manual_slice():
    grid = _grid()
    order = np.asarray([5, 1, 4, 1, 0])
    sub = grid.take_rows(order)
    assert sub.n_rows == 5
    np.testing.assert_array_equal(np.asarray(sub.p_off),
                                  np.asarray(grid.p_off)[order])
    assert sub.prices is grid.prices                  # shared, untouched
    # tuner problem slicing goes through the same implementation
    problem = problem_from_grid(grid)
    probsub = take_rows(problem, order, shared=("prices",))
    np.testing.assert_array_equal(np.asarray(probsub.fixed),
                                  np.asarray(problem.fixed)[order])
    assert probsub.prices is problem.prices


def test_live_grid_take_rows_recurses_into_scenario_grid():
    grid = _grid(n_markets=2, n_policies=2, t=64)
    lgrid = build_live_grid(
        grid, [PolicySpec("ao"), PolicySpec("x3", x=0.03,
                                            off_level=0.3)],
        horizons=(24,), cadences=(2,))
    order = np.arange(lgrid.n_rows)[::-1]
    sub = lgrid.take_rows(order)
    np.testing.assert_array_equal(np.asarray(sub.base_row),
                                  np.asarray(lgrid.base_row)[order])
    np.testing.assert_array_equal(np.asarray(sub.grid.p_off),
                                  np.asarray(lgrid.grid.p_off)[order])
    assert sub.grid.prices is lgrid.grid.prices
    assert sub.horizons == lgrid.horizons             # shared name table


def test_generic_take_rows_refuses_unknown_field_shape():
    grid = _grid()
    bad = dataclasses.replace(grid, period=np.float64(1.0))  # not [B]
    with pytest.raises(TypeError, match="neither a shared field"):
        bad.take_rows(np.asarray([0, 1]))


# ---------------------------------------------------------------------------
# (4) fused soft-dispatch VJP: values + gradients vs oracle and native
# ---------------------------------------------------------------------------

def _dispatch_case(s, t, seed=7):
    r = np.random.default_rng(seed)
    prices = r.normal(80, 40, (s, t)).astype(np.float32)
    power = r.uniform(1.0, 3.0, s).astype(np.float32)
    on = (r.uniform(size=(s, t)) > 0.3).astype(np.float32)
    avail = power[:, None] * (0.2 + 0.8 * on)
    demand = np.full(t, 0.4 * float(avail.sum(axis=0).min()), np.float32)
    keys = segment_keys(prices, 4.0).astype(np.float32)
    order, _ = segment_rank(prices, 4.0)
    return avail, keys, order, demand


FUSED_CASES = [
    # S, T, min_dwell, tau  (odd T exercises the padded final block)
    (3, 64, 0, 5.0),
    (5, 333, 0, 2.0),
    (8, 121, 3, 1.0),
]


@pytest.mark.parametrize("case", FUSED_CASES)
def test_fused_forward_bitwise_vs_ref(case):
    s, t, dwell, tau = case
    avail, keys, order, demand = _dispatch_case(s, t)
    got = np.asarray(soft_dispatch_fused(
        avail, keys, order, demand, tau=tau, min_dwell=dwell,
        use_pallas=False))
    want = np.asarray(soft_dispatch_ref(
        jnp.asarray(avail), jnp.asarray(keys),
        jnp.asarray(order, jnp.int32), jnp.asarray(demand), tau=tau,
        min_dwell=dwell))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", FUSED_CASES)
def test_fused_grads_match_oracle_and_native(case):
    s, t, dwell, tau = case
    avail, keys, order, demand = _dispatch_case(s, t)
    g = np.asarray(
        np.random.default_rng(3).normal(size=(s, t)), np.float32)

    def loss_fused(a, k, d, tv):
        return jnp.sum(soft_dispatch_fused(
            a, k, order, d, tau=tv, min_dwell=dwell,
            use_pallas=False) * g)

    def loss_native(a, k, d, tv):
        return jnp.sum(soft_dispatch(
            a, k, order, d, tau=tv, min_dwell=dwell,
            use_pallas=False) * g)

    da, dk, dd, dt = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(
        jnp.asarray(avail), jnp.asarray(keys), jnp.asarray(demand),
        jnp.asarray(tau, jnp.float32))
    oa, ok, od, ot = soft_dispatch_grad_ref(
        avail, keys, order, demand, g, tau=tau, min_dwell=dwell)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(oa))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(ok))
    np.testing.assert_array_equal(np.asarray(dd), np.asarray(od))
    np.testing.assert_array_equal(np.asarray(dt), np.asarray(ot))
    # and against native autodiff through the scan, in f64 so the
    # comparison is not dominated by f32 round-off
    with jax.enable_x64(True):
        a64 = jnp.asarray(avail, jnp.float64)
        k64 = jnp.asarray(keys, jnp.float64)
        d64 = jnp.asarray(demand, jnp.float64)
        t64 = jnp.asarray(tau, jnp.float64)
        fa, fk, fd, ft = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(
            a64, k64, d64, t64)
        na, nk, nd, nt = jax.grad(loss_native, argnums=(0, 1, 2, 3))(
            a64, k64, d64, t64)
        for f, n in ((fa, na), (fk, nk), (fd, nd), (ft, nt)):
            np.testing.assert_allclose(np.asarray(f), np.asarray(n),
                                       rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("case", [(3, 64, 0, 5.0), (5, 77, 2, 2.0)])
def test_fused_pallas_interpret_matches_xla(case):
    """The Pallas fused pair (interpret mode off-TPU) agrees with the
    XLA fused pair — forward bitwise, gradients to f32 round-off —
    including an odd T that pads the final time block."""
    s, t, dwell, tau = case
    avail, keys, order, demand = _dispatch_case(s, t)

    def loss(a, use_pallas):
        return jnp.sum(soft_dispatch_fused(
            a, keys, order, demand, tau=tau, min_dwell=dwell,
            block_t=32, use_pallas=use_pallas, interpret=True))

    np.testing.assert_array_equal(
        np.asarray(soft_dispatch_fused(
            avail, keys, order, demand, tau=tau, min_dwell=dwell,
            block_t=32, use_pallas=True, interpret=True)),
        np.asarray(soft_dispatch_fused(
            avail, keys, order, demand, tau=tau, min_dwell=dwell,
            use_pallas=False)))
    gp = np.asarray(jax.grad(lambda a: loss(a, True))(
        jnp.asarray(avail)))
    gx = np.asarray(jax.grad(lambda a: loss(a, False))(
        jnp.asarray(avail)))
    assert np.all(np.isfinite(gp))
    np.testing.assert_allclose(gp, gx, rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (5) sharded-but-coupled: psum acceptance, FD gradient, warm start
# ---------------------------------------------------------------------------

def _acceptance_grid():
    """The fixed-seed 256-row grid of tests/test_soft_dispatch.py."""
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


@pytest.mark.skipif(N_DEV < 2, reason="needs >1 device "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
def test_coupled_sharded_objective_ulp_equal_on_acceptance_grid():
    """The tentpole acceptance: the psum-reduced coupled objective
    under shard_map equals the single program's loss to a few ULP on
    the 256-row grid, and its per-row gradient matches to f32
    round-off (the psum transpose is the identity)."""
    grid = _acceptance_grid()
    assert grid.n_rows == 256
    problem = problem_from_grid(grid)
    raw = init_from_grid(grid)
    coupling = dispatch_coupling_from_grid(grid, _DCFG)
    cap = 0.6 * float(np.sum(np.asarray(grid.power)
                             * np.asarray(problem.site_weight)))
    tau = 5.0
    kw = dict(power_cap_mw=cap, dispatch_blend=0.5,
              dispatch_min_dwell=_DCFG.min_dwell_h)

    def single_loss(r):
        loss, _ = soft_objective(r, problem, tau, dispatch=coupling,
                                 reduction="sum", **kw)
        return loss

    n_dev = min(8, N_DEV)
    while grid.n_rows % n_dev:
        n_dev -= 1
    assert n_dev >= 2

    def sharded_loss(r):
        return sharded_soft_objective(r, problem, tau, n_dev=n_dev,
                                      coupling=coupling, **kw)

    single = float(jax.jit(single_loss)(raw))
    sharded = float(jax.jit(sharded_loss)(raw))
    assert abs(sharded - single) <= 4 * np.spacing(np.float32(single)), \
        (single, sharded)

    g1 = jax.grad(single_loss)(raw)
    g2 = jax.grad(sharded_loss)(raw)
    for name in ("raw_off", "raw_gap", "raw_lvl"):
        # f32 round-off only: psum reassociates the per-cell sums, so
        # a few elements move by a couple of ULP of the largest grads
        np.testing.assert_allclose(
            np.asarray(getattr(g2, name)),
            np.asarray(getattr(g1, name)), rtol=1e-4, atol=1e-6,
            err_msg=name)


@pytest.mark.skipif(N_DEV < 2, reason="needs >1 device "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
@pytest.mark.skipif(not F64, reason="FD needs JAX_ENABLE_X64=1")
def test_coupled_sharded_gradient_fd_x64():
    """Central finite differences in f64 confirm the psum-reduced
    gradient end to end (selection softmax, water level, psum'd
    aggregates) on a small coupled fleet."""
    grid = _grid(n_markets=2, n_policies=2, t=96)
    problem = problem_from_grid(grid)
    raw = jax.tree.map(lambda x: jnp.asarray(x, jnp.float64),
                       init_from_grid(grid))
    coupling = dispatch_coupling_from_grid(grid, _DCFG)
    n_dev = 2
    tau = 3.0

    def loss(r):
        return sharded_soft_objective(
            r, problem, tau, n_dev=n_dev, coupling=coupling,
            dispatch_min_dwell=_DCFG.min_dwell_h, fused=False)

    g = jax.grad(loss)(raw)
    eps = 1e-5
    r = np.random.default_rng(11)
    for name in ("raw_off", "raw_gap", "raw_lvl"):
        vec = np.asarray(getattr(raw, name), np.float64)
        for b in r.choice(vec.shape[0], size=2, replace=False):
            e = np.zeros_like(vec)
            e[b] = eps
            hi = loss(raw._replace(**{name: jnp.asarray(vec + e)}))
            lo = loss(raw._replace(**{name: jnp.asarray(vec - e)}))
            fd = (float(hi) - float(lo)) / (2 * eps)
            ad = float(np.asarray(getattr(g, name))[b])
            assert abs(fd - ad) <= 1e-4 * max(1.0, abs(fd)), \
                (name, b, fd, ad)


@pytest.mark.skipif(N_DEV < 2, reason="needs >1 device "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
def test_sharded_plan_tunes_coupled_and_pads_warm_start():
    """An explicit sharded plan runs a coupled tuning loop (the old
    path raised), agrees with the single program per row, and carries a
    warm start through the row padding the shard widths force — the
    silent warm-start drop this PR fixes."""
    grid = _grid(n_markets=2, n_policies=3, t=96)   # 6 rows: pads on 4
    coup = Coupling(dispatch=_DCFG)
    steps = 6
    single = optimize(grid, TuneConfig(
        steps=steps, plan=ExecutionPlan(mode="single"), coupling=coup))
    sharded = optimize(grid, TuneConfig(
        steps=steps, plan=ExecutionPlan(mode="sharded"), coupling=coup))
    for name in ("raw_off", "raw_gap", "raw_lvl"):
        np.testing.assert_allclose(
            np.asarray(getattr(sharded.raw, name)),
            np.asarray(getattr(single.raw, name)), rtol=5e-5,
            atol=5e-5, err_msg=name)

    # warm start actually steers the sharded run: restarting from the
    # tuned params with a tiny budget stays near them, while the cold
    # run from the swept seed lands elsewhere
    warm = optimize(grid, TuneConfig(
        steps=2, plan=ExecutionPlan(mode="sharded"), coupling=coup),
        warm_start=single)
    cold = optimize(grid, TuneConfig(
        steps=2, plan=ExecutionPlan(mode="sharded"), coupling=coup))
    drift_warm = float(np.max(np.abs(np.asarray(warm.raw.raw_off)
                                     - np.asarray(single.raw.raw_off))))
    drift_cold = float(np.max(np.abs(np.asarray(cold.raw.raw_off)
                                     - np.asarray(single.raw.raw_off))))
    assert drift_warm < drift_cold, (drift_warm, drift_cold)
    assert drift_warm < 2.1 * 0.5 * steps  # bounded by lr per step
