"""ScenarioGrid — materialise a B = N x M x K backtesting fleet.

N markets (synthetic `MarketParams` ensembles or raw price matrices),
M `SystemCosts` variants and K policy configurations are stacked into a
flat pytree of B scenario rows that `repro.fleet.engine.backtest` consumes
in one jitted call. Prices stay [N, T] (shared across systems and
policies); every per-row quantity is a [B] vector, so the whole grid for
16 x 8 x 8 x 8760 h is ~a megabyte plus one year of prices per market.

Policies are *operational* (the machinery of `repro.core.policy`): a
two-threshold hysteresis state machine with restart overheads, residual
idle draw and a partial-shutdown capacity level (paper §V-C via
`repro.runtime.elastic`). A policy given as a shutdown fraction ``x`` is
resolved against each market's own empirical PV set (Eq. 1), so one spec
yields a different threshold price per market — exactly how an operator
would deploy the same plan across sites.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import execution
from repro.core.tco import SystemCosts
from repro.energy.markets import MarketParams, generate_market
from repro.obs.profiling import profiled
from repro.runtime.elastic import capacity_plan


@dataclasses.dataclass(frozen=True)
class PolicySpec:
    """One operational shutdown policy, shared across markets/systems.

    Exactly one of ``x`` (shutdown fraction, resolved per market) or
    ``p_off`` (absolute threshold price) must be set; ``x <= 0`` or
    ``p_off=None`` with ``x=None`` means always-on. ``hysteresis`` < 1
    resumes only once the price falls below ``hysteresis * p_off``
    (sign-safe for negative thresholds). ``off_level`` is the capacity
    fraction kept online while "off" (partial shutdown, §V-C);
    ``idle_frac`` the residual draw of the shut-down remainder.
    """

    name: str
    x: Optional[float] = None
    p_off: Optional[float] = None
    hysteresis: float = 1.0
    off_level: float = 0.0
    idle_frac: float = 0.0
    restart_energy_mwh: float = 0.0
    restart_time_h: float = 0.0

    def __post_init__(self):
        if self.x is not None and self.p_off is not None:
            raise ValueError(f"policy {self.name!r}: give x or p_off, "
                             "not both")
        if not 0.0 <= self.off_level < 1.0:
            raise ValueError(f"policy {self.name!r}: off_level must be "
                             "in [0, 1)")
        if self.x is not None and not 0.0 <= self.x < 1.0:
            raise ValueError(f"policy {self.name!r}: x is a shutdown "
                             "fraction and must be in [0, 1)")
        if not 0.0 < self.hysteresis <= 1.0:
            raise ValueError(f"policy {self.name!r}: hysteresis must be "
                             "in (0, 1] (p_on may not exceed p_off)")


def elastic_policy(name: str, *, level: float, dp_total: int,
                   **spec_kwargs) -> PolicySpec:
    """A partial-shutdown policy whose off-capacity is snapped to a
    *realisable* data-parallel fraction via `repro.runtime.elastic`:
    keeping ``level`` of a ``dp_total``-replica job means keeping
    ``capacity_plan(level, dp_total).level`` of its power."""
    plan = capacity_plan(level, dp_total)
    return PolicySpec(name=name, off_level=plan.level, **spec_kwargs)


@dataclasses.dataclass(frozen=True)
class ScenarioGrid:
    """Stacked scenario rows, ordered b = (n*M + m)*K + k."""

    prices: jnp.ndarray        # [N, T] hourly prices, shared across rows
    market_idx: jnp.ndarray    # [B] int32 row -> market n
    system_idx: jnp.ndarray    # [B] int32 row -> system m
    policy_idx: jnp.ndarray    # [B] int32 row -> policy k
    fixed: jnp.ndarray         # [B] F   (SystemCosts per row)
    power: jnp.ndarray         # [B] C
    period: jnp.ndarray        # [B] T hours
    p_on: jnp.ndarray          # [B] resume-below price
    p_off: jnp.ndarray         # [B] shutdown-above price
    off_level: jnp.ndarray     # [B] capacity retained while off
    idle_frac: jnp.ndarray     # [B] residual draw of the off part
    restart_energy_mwh: jnp.ndarray  # [B]
    restart_time_h: jnp.ndarray      # [B]
    market_names: tuple = ()
    system_names: tuple = ()
    policy_names: tuple = ()
    # optional `repro.workload.Workload` spec (duck-typed to avoid the
    # import cycle): None keeps every engine on the exogenous-demand
    # programs bit-identically; set, `workload_backtest`/`summarize`/
    # `optimize` couple the rows to sampled request traces
    workload: Optional[object] = None

    @property
    def n_rows(self) -> int:
        return int(self.market_idx.shape[0])

    @property
    def n_markets(self) -> int:
        return int(self.prices.shape[0])

    @property
    def n_systems(self) -> int:
        return len(self.system_names)

    @property
    def n_policies(self) -> int:
        return len(self.policy_names)

    @property
    def n_hours(self) -> int:
        return int(self.prices.shape[1])

    # fields shared across rows, NOT permuted by take_rows; everything
    # else must be a [B]-leading array or take_rows refuses to guess
    SHARED_FIELDS = ("prices", "market_names", "system_names",
                     "policy_names", "workload")

    def take_rows(self, order: np.ndarray) -> "ScenarioGrid":
        """Row-permuted view (shared fields stay); row order is an
        implementation detail the report layer must not depend on.

        Delegates to the one shape-driven `repro.execution.take_rows`
        (shared with `tune.optimizer`'s problem slicing and
        `LiveGrid.take_rows`): every field outside `SHARED_FIELDS` is
        carried through the permutation — a future per-row field is
        picked up automatically, and a field that is neither shared nor
        [B]-leading raises instead of being silently dropped
        (`tests/test_fleet.py` pins this against ``dataclasses.fields``).
        """
        return execution.take_rows(self, order, shared=self.SHARED_FIELDS,
                                   n_rows=self.n_rows)


def row_chunks(n_rows: int, chunk: int) -> list[np.ndarray]:
    """Equal-size row-index slices covering ``n_rows``, the last padded
    by repeating row 0.

    The single source of the chunked-evaluation idiom (fleet backtest,
    tuner loop, hard re-eval): equal slice sizes mean one compile shape,
    and because every per-row computation in those paths is independent
    of its batch, the padding rows cannot perturb the real ones — they
    are simply dropped again by `concat_rows`.
    """
    n_chunks = -(-n_rows // chunk)
    idx = np.concatenate([np.arange(n_rows),
                          np.zeros(n_chunks * chunk - n_rows, np.int64)])
    return [idx[j * chunk:(j + 1) * chunk] for j in range(n_chunks)]


def concat_rows(parts: list, n_rows: int):
    """Concatenate per-chunk pytrees along the row axis and trim the
    `row_chunks` padding. Works on bare arrays and on any pytree of
    [chunk]-leading leaves (FleetReport, PolicyParams, ...)."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs)[:n_rows], *parts)


def _resolve_threshold(prices_desc: np.ndarray, spec: PolicySpec) -> float:
    """Shutdown threshold of ``spec`` on one market (descending-sorted
    prices): Eq. (1)'s quantile for fraction specs, the given absolute
    price otherwise, +inf for always-on."""
    if spec.x is not None:
        n = prices_desc.shape[0]
        if spec.x <= 0.0:
            return np.inf
        m = int(np.clip(round(spec.x * n), 1, n - 1))
        return float(prices_desc[m - 1])
    if spec.p_off is None:
        return np.inf
    return float(spec.p_off)


def _resume_threshold(p_off: float, hysteresis: float) -> float:
    """p_on <= p_off even for negative prices: back off by
    (1 - hysteresis) of the threshold's magnitude."""
    if not np.isfinite(p_off):
        return p_off
    return p_off - (1.0 - hysteresis) * abs(p_off)


def build_grid(markets: Union[Sequence[MarketParams], np.ndarray],
               systems: Sequence[SystemCosts],
               policies: Sequence[PolicySpec],
               market_names: Optional[Sequence[str]] = None,
               system_names: Optional[Sequence[str]] = None,
               workload=None) -> ScenarioGrid:
    """Materialise the B = N*M*K scenario grid.

    ``markets``: either MarketParams (each generated via
    `repro.energy.markets.generate_market`) or an [N, T] price matrix
    (e.g. real SMARD traces). All markets must share T; all systems are
    backtested over the same period. ``workload`` (a
    `repro.workload.Workload`) couples the grid to sampled request
    traces wherever the grid flows; None keeps today's exogenous-demand
    programs untouched.
    """
    if len(systems) == 0 or len(policies) == 0:
        raise ValueError("need at least one system and one policy")
    if isinstance(markets, (np.ndarray, jnp.ndarray)):
        with profiled("grid.fetch", bytes=int(markets.size) * 4):
            prices = np.asarray(markets, np.float32)
        if prices.ndim != 2:
            raise ValueError("price matrix must be [n_markets, n_hours]")
    else:
        if len(markets) == 0:
            raise ValueError("need at least one market")
        prices = np.stack([np.asarray(generate_market(mp).prices,
                                      np.float32) for mp in markets])
    n, t = prices.shape
    m_sys, k_pol = len(systems), len(policies)

    # per-(market, policy) thresholds from each market's own PV set
    with profiled("grid.sort", markets=n, hours=t):
        sorted_desc = -np.sort(-prices, axis=1)
    # the sort puts +inf first and NaN and -inf last; a bad hour must stop
    # here, since the scan reads prices through sums over markets
    bad = ~np.isfinite(sorted_desc[:, [0, -1]]).all(axis=1)
    if bad.any():
        raise ValueError(f"prices must be finite: market(s) "
                         f"{np.flatnonzero(bad).tolist()} hold NaN or inf")
    p_off_nk = np.empty((n, k_pol), np.float32)
    p_on_nk = np.empty((n, k_pol), np.float32)
    with profiled("grid.thresholds", resolutions=n * k_pol):
        for k, spec in enumerate(policies):
            for i in range(n):
                off = _resolve_threshold(sorted_desc[i], spec)
                p_off_nk[i, k] = off
                p_on_nk[i, k] = _resume_threshold(off, spec.hysteresis)

    with profiled("grid.fields", rows=n * m_sys * k_pol):
        mi, si, pi = np.meshgrid(np.arange(n), np.arange(m_sys),
                                 np.arange(k_pol), indexing="ij")
        mi, si, pi = (a.reshape(-1).astype(np.int32) for a in (mi, si, pi))

        sys_field = lambda fn: np.asarray(  # noqa: E731
            [float(fn(s)) for s in systems], np.float32)[si]
        pol_field = lambda fn: np.asarray(  # noqa: E731
            [float(fn(p)) for p in policies], np.float32)[pi]
        fields = dict(
            prices=prices, market_idx=mi, system_idx=si, policy_idx=pi,
            fixed=sys_field(lambda s: s.F),
            power=sys_field(lambda s: s.C),
            period=sys_field(lambda s: s.T),
            p_on=p_on_nk[mi, pi], p_off=p_off_nk[mi, pi],
            off_level=pol_field(lambda p: p.off_level),
            idle_frac=pol_field(lambda p: p.idle_frac),
            restart_energy_mwh=pol_field(lambda p: p.restart_energy_mwh),
            restart_time_h=pol_field(lambda p: p.restart_time_h))

    with profiled("grid.upload",
                  bytes=sum(int(a.nbytes) for a in fields.values()),
                  arrays=len(fields)):
        fields = {k: jnp.asarray(a) for k, a in fields.items()}

    if market_names is None:
        market_names = tuple(f"market{i}" for i in range(n))
    if system_names is None:
        system_names = tuple(f"system{i}" for i in range(m_sys))

    return ScenarioGrid(
        **fields,
        market_names=tuple(market_names),
        system_names=tuple(system_names),
        policy_names=tuple(p.name for p in policies),
        workload=workload,
    )
