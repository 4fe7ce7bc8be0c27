"""Batched gradient tuning of shutdown policies over a whole fleet grid.

`optimize` turns every row of a `ScenarioGrid` into an independent
optimization problem: the row's three policy variables (threshold,
hysteresis gap, off-capacity level — reparameterized unconstrained, see
`repro.tune.objective`) descend the temperature-relaxed CPC objective
simultaneously, one jitted `lax.scan` over optimization steps with the
whole [B]-row gradient computed in a single backward pass through the
soft scan — by default the fused, checkpointed custom VJP of
`repro.kernels.soft_scan_vjp` (``TuneConfig.fused``), which replaces
native autodiff through the associative scan with a block-local
recompute and cuts both the backward's arithmetic and its residual
memory.

The update rule *is* `repro.optim.adamw.adamw_update` — the same code
path that trains the models — vmapped over rows so each row carries its
own Adam moments and (optionally) its own per-row gradient clip.

The hot loop (`tune_loop`) is one compiled program: the τ-annealing
schedule, every Adam step, and the final hard (τ → 0) re-evaluation all
run inside a single jit with the raw-parameter carry donated, so a
tuning run is one dispatch and the optimizer state never round-trips.
Because the per-row gradients are batch-independent (sum-reduction, see
`soft_objective`), the loop also scales out without changing results:

  * row chunking — ``TuneConfig.chunk_rows`` tunes the grid in fixed
    row slices (padded to one compile shape), bounding peak memory so
    B ~ 10^5 grids tune on one host — *bit-identical* to the one-shot
    program (every chunk compiles to the same shape);
  * ``shard_map`` over B — with more than one device (including CPU
    virtual devices via ``--xla_force_host_platform_device_count``),
    rows are split across a 1-D `repro.parallel.row_mesh` and tuned in
    parallel. Same math, but XLA codegen depends on the shard width, so
    agreement with the single-device program is ULP-level rather than
    bitwise (shards narrower than 2 rows are never created).

Temperature annealing: the sigmoid temperature follows a geometric
schedule from ``tau_start`` (smooth, wide basins — gradients see far
across the price distribution) down to ``tau_end`` (nearly hard — the
soft objective tracks the real discrete-switching CPC). After the last
step the result is re-evaluated under the *hard* scan (tau -> 0
exactly), and each row keeps the best of {tuned params, its own swept
policy, the best swept policy of its (market, system) cell} — so the
reported CPC can never be worse than the swept grid it started from.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dispatch import (DispatchConfig, DispatchInfeasible,
                            build_problem)
from repro.dispatch import dispatch as dispatch_solve
from repro.execution import (Coupling, ExecutionPlan, take_rows,
                             validate_plan_coupling)
from repro.fleet.engine import backtest, fleet_costs
from repro.fleet.grid import concat_rows, row_chunks
from repro.kernels.ref import fleet_scan_ref
from repro.optim.adamw import AdamWConfig, AdamWState, adamw_update
from repro.parallel.axes import row_mesh
from repro.tune.objective import (DispatchCoupling, PhysicalPolicy,
                                  PolicyParams, TuneProblem, cell_index,
                                  dispatch_coupling_from_grid,
                                  init_from_grid, inverse_transform,
                                  problem_from_grid, soft_objective,
                                  transform)

from jax.sharding import PartitionSpec as P


_PLAN_FIELD_DEFAULTS = {"chunk_rows": 0, "shard": True}
_COUPLING_FIELD_DEFAULTS = {
    "power_cap_mw": None, "min_up_hours": None, "penalty_weight": 10.0,
    "dispatch": None, "dispatch_soft": None, "dispatch_blend": 0.5,
    "dispatch_mw_scale": 0.05}


@dataclasses.dataclass(frozen=True)
class TuneConfig:
    """Hyperparameters of a fleet tuning run (hashable — used as a jit
    static argument).

    Execution layout and fleet coupling are configured by the
    `repro.execution` pair: ``plan`` (`ExecutionPlan`: single / chunked
    / sharded, device cap, reproducibility contract) and ``coupling``
    (`Coupling`: power cap, aggregate-compute floor, dispatch-aware
    term, hard-dispatch re-scoring). The pre-redesign spellings
    (``chunk_rows`` / ``shard`` / ``power_cap_mw`` / ``min_up_hours`` /
    ``penalty_weight`` / ``dispatch`` / ``dispatch_soft`` /
    ``dispatch_blend`` / ``dispatch_mw_scale``) still work for one
    release: they emit a `DeprecationWarning` at construction and
    forward into ``resolved_plan`` / ``resolved_coupling``, which is
    all the tuner reads. Mixing an explicit ``plan=``/``coupling=``
    with the old spellings it replaces raises. The chunk-under-coupling
    legality rule is a constructor invariant
    (`repro.execution.validate_plan_coupling`), raised here instead of
    deep inside the hot-loop dispatcher.
    """

    steps: int = 300
    lr: float = 0.5              # raw-space Adam step (price units for
                                 # raw_off; Adam normalizes per-coordinate)
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    clip_norm: float = 0.0       # per-row grad clip; 0 disables. The
                                 # clipped quantity is the row's
                                 # gradient of its *own* CPC ratio
                                 # (sum-reduction, B-independent);
                                 # values calibrated against the PR-2/3
                                 # mean-reduction loop scale by 1/B
    tau_start: float = 30.0      # EUR/MWh-scale smoothing at the start
    tau_end: float = 0.3         # nearly hard by the end
    # hot-loop implementation knobs
    fused: bool = True           # checkpointed custom-VJP soft scan +
                                 # fused soft-dispatch VJP (False:
                                 # native autodiff — the PR-3 baseline,
                                 # kept for A/B benchmarks)
    block_t: int = 256           # checkpoint block length (hours)
    # deprecated execution spellings (forward into resolved_plan)
    chunk_rows: int = 0
    shard: bool = True
    eval_stages: int = 4         # hard (tau -> 0) re-evaluations spread
                                 # over the anneal: the scan splits into
                                 # this many segments (same per-step
                                 # ops; trajectories agree to float
                                 # round-off across stage counts) with
                                 # a hard CPC re-eval at each boundary
                                 # -> TuneResult.stage_cpc; clamped to
                                 # [1, steps]
    # deprecated coupling spellings (forward into resolved_coupling)
    power_cap_mw: Optional[float] = None
    min_up_hours: Optional[float] = None
    penalty_weight: float = 10.0
    dispatch: Optional[DispatchConfig] = None
    dispatch_soft: Optional[DispatchConfig] = None
    dispatch_blend: float = 0.5
    dispatch_mw_scale: float = 0.05
    # optional `repro.workload.Workload` (duck-typed, hashable frozen
    # dataclass — safe as part of this jit-static config): the soft
    # objective adds its SLO-aware work-ledger term and `optimize`
    # selects candidates by *realized* workload cost (energy + deferral
    # + drop) instead of bare CPC. None falls back to ``grid.workload``;
    # neither set keeps today's programs untouched
    workload: Optional[object] = None
    # the redesigned config surface (None: derive from the fields above)
    plan: Optional[ExecutionPlan] = None
    coupling: Optional[Coupling] = None

    def __post_init__(self):
        plan_old = [k for k, d in _PLAN_FIELD_DEFAULTS.items()
                    if getattr(self, k) != d]
        coup_old = [k for k, d in _COUPLING_FIELD_DEFAULTS.items()
                    if getattr(self, k) != d]
        if self.plan is not None and plan_old:
            raise ValueError(
                f"TuneConfig: pass plan= or the deprecated "
                f"{'/'.join(plan_old)}, not both")
        if self.coupling is not None and coup_old:
            raise ValueError(
                f"TuneConfig: pass coupling= or the deprecated "
                f"{'/'.join(coup_old)}, not both")
        for k in plan_old:
            warnings.warn(
                f"TuneConfig.{k} is deprecated — pass "
                f"plan=repro.execution.ExecutionPlan(...) instead",
                DeprecationWarning, stacklevel=3)
        for k in coup_old:
            warnings.warn(
                f"TuneConfig.{k} is deprecated — pass "
                f"coupling=repro.execution.Coupling(...) instead",
                DeprecationWarning, stacklevel=3)
        # constructor invariants: ExecutionPlan validates chunk_rows
        # (width-1 chunks etc.), validate_plan_coupling the
        # chunk-under-coupling contradiction — both raised here, at
        # assembly time, not deep inside the hot-loop dispatcher
        validate_plan_coupling(self.resolved_plan,
                               self.resolved_coupling,
                               context="TuneConfig")

    @property
    def resolved_plan(self) -> ExecutionPlan:
        """The `ExecutionPlan` the tuner executes: ``plan`` when given,
        else the deprecated fields' equivalent (``chunk_rows`` ->
        chunked/bitwise, ``shard=False`` -> single, else auto)."""
        if self.plan is not None:
            return self.plan
        if self.chunk_rows:
            return ExecutionPlan(mode="chunked",
                                 chunk_rows=self.chunk_rows,
                                 contract="bitwise")
        if not self.shard:
            return ExecutionPlan(mode="single")
        return ExecutionPlan()

    @property
    def resolved_coupling(self) -> Coupling:
        """The `Coupling` in force (never None — an unbound `Coupling()`
        when nothing couples): ``coupling`` when given, else the
        deprecated fields' equivalent."""
        if self.coupling is not None:
            return self.coupling
        return Coupling(power_cap_mw=self.power_cap_mw,
                        min_up_hours=self.min_up_hours,
                        penalty_weight=self.penalty_weight,
                        dispatch=self.dispatch_soft,
                        dispatch_blend=self.dispatch_blend,
                        dispatch_mw_scale=self.dispatch_mw_scale,
                        reeval=self.dispatch)

    def _replace(self, **kw) -> "TuneConfig":
        """NamedTuple-style replace (the pre-redesign TuneConfig was a
        NamedTuple; callers keep working)."""
        return dataclasses.replace(self, **kw)


class TuneResult(NamedTuple):
    """Output of `optimize` (per-row arrays of shape [B])."""

    params: PhysicalPolicy       # selected per-row policy (hard-eval best)
    raw: PolicyParams            # final raw params of the gradient run
    cpc: np.ndarray              # hard CPC of the selected policy
    cpc_tuned: np.ndarray        # hard CPC of the gradient-tuned params
    cpc_swept: np.ndarray        # engine CPC of the row's own swept policy
    cpc_swept_best: np.ndarray   # best engine CPC in the row's cell
    improvement_vs_best: np.ndarray   # 1 - cpc / cpc_swept_best
    improvement_vs_own: np.ndarray    # 1 - cpc / cpc_swept
    source: np.ndarray           # 0 = tuned, 1 = own swept, 2 = cell best
    history: dict                # per-step arrays: loss, tau, penalty
    # mean hard CPC at each anneal-stage boundary ([cfg.eval_stages],
    # last entry == mean(cpc_tuned)) — the convergence curve the soft
    # loss cannot show (chunked runs report the mean over row chunks)
    stage_cpc: Optional[np.ndarray] = None
    # feasible-dispatch re-evaluation (None unless cfg.dispatch or
    # cfg.dispatch_soft given): {"cpc_tuned", "cpc_swept", "chosen",
    # "tuned", "swept", "rows", "site_names", "infeasible_*"} where
    # "tuned"/"swept" are repro.dispatch.DispatchResult and "rows" the
    # grid rows operated as sites
    dispatch: Optional[dict] = None
    # total row-steps the finite-step guard rejected (0 on any healthy
    # run; per-step counts in history["guard_rejects"])
    guard_count: int = 0
    # [B] realized workload cost (energy + deferral + drop, EUR, mean
    # over the shared demand draws) of the *selected* policy — None
    # unless a Workload was configured; when set, ``source`` was chosen
    # by this yardstick instead of bare CPC
    workload_cost: Optional[np.ndarray] = None


def _tau_schedule(cfg: TuneConfig) -> jnp.ndarray:
    """Geometric anneal tau_start -> tau_end over ``cfg.steps``."""
    if cfg.steps == 1:
        return jnp.asarray([cfg.tau_start], jnp.float32)
    i = jnp.arange(cfg.steps, dtype=jnp.float32) / (cfg.steps - 1)
    return cfg.tau_start * (cfg.tau_end / cfg.tau_start) ** i


def _hard_cpc_rows(p_on, p_off, off_level, problem: TuneProblem
                   ) -> jnp.ndarray:
    """Hard (tau -> 0) CPC of arbitrary per-row policy variables under
    each row's own hardware parameters — the engine's exact scan + cost
    path. Traced into `tune_loop` (and into the jitted `hard_cpc`)."""
    p_rows = problem.row_prices()
    scan = fleet_scan_ref(p_rows, p_on, p_off, off_level,
                          problem.idle_frac)
    return fleet_costs(
        scan, price_sum=problem.price_sum, fixed=problem.fixed,
        power=problem.power, period=problem.period,
        restart_energy_mwh=problem.restart_energy_mwh,
        restart_time_h=problem.restart_time_h,
        n_samples=p_rows.shape[1]).cpc


hard_cpc = jax.jit(_hard_cpc_rows)


def _stage_bounds(cfg: TuneConfig) -> list:
    """Step indices of the anneal-stage boundaries: ``eval_stages``
    near-equal segments of [0, steps] (strictly increasing — clamped to
    at most one stage per step)."""
    stages = max(1, min(int(cfg.eval_stages), cfg.steps))
    return [(i * cfg.steps) // stages for i in range(stages + 1)]


def _loop_body(raw0: PolicyParams, problem: TuneProblem, cfg: TuneConfig,
               coupling: Optional[DispatchCoupling] = None,
               telemetry: bool = False,
               axis_name: Optional[str] = None,
               scale_rows: Optional[int] = None):
    """The tuner hot loop: annealed Adam scan + hard re-evaluations.

    Traced under plain jit (single program), under `shard_map` (one
    shard of rows), and per chunk — identical per-row math in all
    three, which is what makes the scaled-out paths bit-consistent
    (``coupling`` is non-None in the single program and, since the
    psum rework, in the sharded path — never in a chunk).

    The step scan runs as ``cfg.eval_stages`` back-to-back `lax.scan`
    segments over the one tau schedule — the per-step ops are the same,
    so trajectories agree across stage counts to float round-off
    (segment boundaries change XLA fusion, hence ULP-level rather than
    bitwise) — with the *hard* (tau -> 0)
    CPC re-evaluated at each boundary (``history["stage_cpc"]``,
    [stages]; its last entry is the final hard re-eval, so the stage
    curve is free). ``telemetry`` adds per-step grad-norm / clip-
    fraction side-outputs to the history — observers of values the
    update already computes, never inputs to it, keeping the tuned
    parameters bit-identical (asserted in tests/test_obs.py).

    ``axis_name`` (set when traced inside the sharded path's
    `shard_map`) flows into `soft_objective`, whose fleet aggregates
    then psum-reduce across shards — coupled objectives shard;
    ``scale_rows`` pins the coupled terms' B-scale at the real global
    row count. The per-shard history loss removes the coupled term's
    cross-shard duplication (every shard carries the full global term)
    so the shard-averaged history matches the single program's.
    Returns ``(raw_f, history, cpc_tuned)``.
    """
    b = raw0.raw_off.shape[0]
    step = _make_step(problem, cfg, coupling, b, telemetry=telemetry,
                      axis_name=axis_name, scale_rows=scale_rows)
    taus = _tau_schedule(cfg)
    bounds = _stage_bounds(cfg)
    carry = _init_carry(raw0)
    hists, stage_cpc = [], []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        carry, h = jax.lax.scan(step, carry, taus[lo:hi])
        hists.append(h)
        ph = transform(carry[0])
        cpc_rows = _hard_cpc_rows(ph.p_on, ph.p_off, ph.off_level,
                                  problem)
        stage_cpc.append(jnp.mean(cpc_rows))
    raw_f = carry[0]
    hist = hists[0] if len(hists) == 1 else \
        jax.tree.map(lambda *xs: jnp.concatenate(xs), *hists)
    hist["stage_cpc"] = jnp.stack(stage_cpc)
    # cpc_rows from the last stage IS the final hard re-evaluation
    return raw_f, hist, cpc_rows


_LR_BACKOFF_FLOOR = 2.0 ** -10   # per-row lr multiplier never decays
                                 # below this — a row that recovers
                                 # still moves


def _init_carry(raw0: PolicyParams):
    """The hot loop's scan carry: raw params, per-row Adam moments, and
    the per-row guard lr multiplier (1.0 until a step is rejected)."""
    b = raw0.raw_off.shape[0]
    state0 = AdamWState(step=jnp.zeros((), jnp.int32),
                        mu=jax.tree.map(jnp.zeros_like, raw0),
                        nu=jax.tree.map(jnp.zeros_like, raw0))
    return (raw0, state0, jnp.ones((b,), jnp.float32))


def _make_step(problem: TuneProblem, cfg: TuneConfig,
               coupling: Optional[DispatchCoupling], b: int, *,
               telemetry: bool = False,
               axis_name: Optional[str] = None,
               scale_rows: Optional[int] = None):
    """Build the per-step closure of the Adam scan (shared by
    `_loop_body` and the stage-wise `tune_loop_checkpointed` segments,
    so both trace the *same* per-step program).

    Every step carries a branchless finite-step guard: a row whose soft
    CPC ratio or gradient leaves a non-finite value (a NaN price gap
    reaching the objective, an overflowing coupled term mid-storm) has
    its gradient zeroed, its parameters and Adam moments held, and its
    per-row lr multiplier halved (floor ``_LR_BACKOFF_FLOOR``) — the
    row re-enters at reduced step size instead of poisoning the carry.
    On an all-finite run every guard op is an exact arithmetic identity
    (``where(True, x, _)``, ``where(lr == 1.0, new, _)``), so healthy
    trajectories are bit-identical to the unguarded loop (asserted in
    tests/test_faults.py). The per-step reject count streams out as
    ``history["guard_rejects"]``.
    """
    rc = cfg.resolved_coupling
    opt = AdamWConfig(lr=cfg.lr, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
                      weight_decay=0.0, clip_norm=cfg.clip_norm)

    def row_update(g, st, p):
        new_p, new_st, _ = adamw_update(g, st, p, opt)
        return new_p, new_st

    state_axes = AdamWState(step=None, mu=0, nu=0)
    vupdate = jax.vmap(row_update, in_axes=(0, state_axes, 0),
                       out_axes=(0, state_axes))

    grad_fn = jax.value_and_grad(soft_objective, has_aux=True)
    min_dwell = rc.dispatch.min_dwell_h \
        if rc.dispatch is not None else 0
    wl = getattr(cfg, "workload", None)
    # the [T] mean demand profile is host-side numpy (constant-folded
    # into the traced program); each row serves it independently, so
    # the workload term stays per-row separable on every plan path
    wl_demand = None if wl is None else jnp.asarray(
        wl.mean_demand_mw(int(problem.prices.shape[1])), jnp.float32)

    def step(carry, tau):
        raw, st, lr_scale = carry
        (loss, aux), grads = grad_fn(
            raw, problem, tau, power_cap_mw=rc.power_cap_mw,
            min_up_hours=rc.min_up_hours,
            penalty_weight=rc.penalty_weight,
            dispatch=coupling, dispatch_blend=rc.dispatch_blend,
            dispatch_min_dwell=min_dwell,
            dispatch_mw_scale=rc.dispatch_mw_scale,
            dispatch_fused=cfg.fused, relief=rc.relief_config,
            workload=wl, workload_demand=wl_demand,
            fused=cfg.fused, block_t=cfg.block_t, reduction="sum",
            axis_name=axis_name, scale_rows=scale_rows)
        if axis_name is None:
            hist_loss = loss / b
        else:
            # every shard's loss carries the full global coupled term;
            # keep 1/n_sh of it so the caller's shard average (which
            # divides the separable part by B through the b-per-shard
            # denominators) reproduces the single program's loss/B
            n_sh = jax.lax.psum(1, axis_name)
            hist_loss = (loss - aux["coupled"] * (1.0 - 1.0 / n_sh)) / b
        # finite-step guard: per-row accept mask over the row's own CPC
        # ratio and its three gradient components
        ok = (jnp.isfinite(aux["ratio"]) & jnp.isfinite(grads.raw_off)
              & jnp.isfinite(grads.raw_gap)
              & jnp.isfinite(grads.raw_lvl))                  # [B]
        out = {"loss": hist_loss, "tau": tau,
               "penalty": aux["penalty"],
               "dispatch_ratio": aux["dispatch_ratio"],
               "guard_rejects": jnp.sum((~ok).astype(jnp.float32))}
        if telemetry:
            # observers only: read the gradients the update consumes,
            # feed nothing back
            norm = jnp.sqrt(grads.raw_off ** 2 + grads.raw_gap ** 2
                            + grads.raw_lvl ** 2)            # [B]
            out["grad_norm"] = jnp.mean(norm)
            out["clip_frac"] = (
                jnp.mean((norm > cfg.clip_norm).astype(norm.dtype))
                if cfg.clip_norm else jnp.zeros((), norm.dtype))
        g_safe = jax.tree.map(lambda g: jnp.where(ok, g, 0.0), grads)
        new_p, new_st = vupdate(g_safe, st, raw)
        # backed-off rows blend toward the Adam target; where(lr == 1)
        # selects new_p itself because raw + 1.0 * (new_p - raw) is NOT
        # a bitwise identity
        applied = jax.tree.map(
            lambda n, r: jnp.where(lr_scale == 1.0, n,
                                   r + lr_scale * (n - r)), new_p, raw)
        raw_new = jax.tree.map(lambda a, r: jnp.where(ok, a, r),
                               applied, raw)
        st_new = AdamWState(
            step=new_st.step,       # global step counts every attempt
            mu=jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                            new_st.mu, st.mu),
            nu=jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                            new_st.nu, st.nu))
        lr_new = jnp.where(ok, lr_scale,
                           jnp.maximum(lr_scale * 0.5,
                                       _LR_BACKOFF_FLOOR))
        return (raw_new, st_new, lr_new), out

    return step


@functools.partial(jax.jit, static_argnames=("cfg", "telemetry"),
                   donate_argnums=(0,))
def tune_loop(raw0: PolicyParams, problem: TuneProblem,
              coupling: Optional[DispatchCoupling] = None, *,
              cfg: TuneConfig, telemetry: bool = False):
    """One compiled tuning program: τ-annealed Adam over all rows plus
    the staged hard re-evaluations, with the raw-parameter carry donated
    (the Adam scan reuses its buffers instead of allocating fresh ones
    each call). ``coupling`` (from `dispatch_coupling_from_grid`)
    switches on the dispatch-aware fleet term. ``telemetry`` is static:
    False (the default, and whenever `repro.obs` is disabled) compiles
    the exact pre-telemetry program with no extra side-outputs. This is
    the object `benchmarks/bench_tune.py` times."""
    return _loop_body(raw0, problem, cfg, coupling, telemetry)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "lo", "hi", "telemetry"))
def _stage_segment(carry, problem: TuneProblem,
                   coupling: Optional[DispatchCoupling] = None, *,
                   cfg: TuneConfig, lo: int, hi: int,
                   telemetry: bool = False):
    """One anneal stage of the checkpointed runner: the Adam scan over
    ``taus[lo:hi]`` plus the boundary's hard re-evaluation, jitted per
    (cfg, stage window). Same per-step program as `tune_loop` (built by
    `_make_step`); stage boundaries compile separately, so the
    checkpointed trajectory's bit-identity contract is against *itself*
    (resumed == uninterrupted), not against the single-jit loop —
    XLA fusion differs across program boundaries."""
    b = carry[0].raw_off.shape[0]
    step = _make_step(problem, cfg, coupling, b, telemetry=telemetry)
    taus = _tau_schedule(cfg)
    carry, hist = jax.lax.scan(step, carry, taus[lo:hi])
    ph = transform(carry[0])
    cpc_rows = _hard_cpc_rows(ph.p_on, ph.p_off, ph.off_level, problem)
    return carry, hist, cpc_rows


def _ckpt_template(carry, cfg: TuneConfig, n_steps_done: int,
                   n_stages_done: int, b: int, telemetry: bool) -> dict:
    """Zero-filled pytree matching a `tune_loop_checkpointed` save after
    ``n_stages_done`` stages — what `load_checkpoint` restores into."""
    keys = ["dispatch_ratio", "guard_rejects", "loss", "penalty", "tau"]
    if telemetry:
        keys += ["clip_frac", "grad_norm"]
    return {
        "carry": carry,
        "hist": {k: np.zeros((n_steps_done,), np.float32)
                 for k in keys},
        "stage_cpc": np.zeros((n_stages_done,), np.float32),
        "cpc_rows": np.zeros((b,), np.float32),
    }


def tune_loop_checkpointed(raw0: PolicyParams, problem: TuneProblem,
                           coupling: Optional[DispatchCoupling] = None,
                           *, cfg: TuneConfig, directory,
                           telemetry: bool = False, keep: int = 2):
    """`tune_loop` as resumable anneal stages with the full optimizer
    carry checkpointed at every stage boundary (`repro.checkpoint`).

    The scan runs stage by stage (`_stage_segment`, one jit per stage
    window); after each stage the carry — raw params, per-row Adam
    moments, the guard's lr multipliers — plus the accumulated history
    and stage CPCs land under ``directory`` via `CheckpointManager`
    (npz round-trips float bits exactly). A rerun over the same
    directory restores the newest stage and continues: a killed run
    resumed this way is *bit-identical* to one that never died
    (asserted in tests/test_faults.py), because the restored carry is
    the exact bytes the uninterrupted run would have carried and every
    remaining stage re-traces the same program. Returns
    ``(raw_f, history, cpc_tuned)`` like `tune_loop`."""
    from repro.checkpoint import CheckpointManager

    raw0 = PolicyParams(*(jnp.asarray(a) for a in raw0))
    b = raw0.raw_off.shape[0]
    bounds = _stage_bounds(cfg)
    n_stages = len(bounds) - 1
    mgr = CheckpointManager(directory, keep=keep)
    carry = _init_carry(raw0)
    hists: list = []
    stage_cpc: list = []
    cpc_rows = None
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        k = min(int(latest), n_stages)
        tree, _ = mgr.restore(
            _ckpt_template(carry, cfg, bounds[k], k, b, telemetry),
            step=latest)
        carry = tree["carry"]
        hists = [tree["hist"]]
        stage_cpc = [np.float32(v) for v in tree["stage_cpc"]]
        cpc_rows = tree["cpc_rows"]
        start = k
    for k in range(start, n_stages):
        carry, h, cpc_rows = _stage_segment(
            carry, problem, coupling, cfg=cfg, lo=bounds[k],
            hi=bounds[k + 1], telemetry=telemetry)
        hists.append({kk: np.asarray(v) for kk, v in h.items()})
        stage_cpc.append(np.float32(np.asarray(jnp.mean(cpc_rows))))
        hist_acc = {kk: np.concatenate([np.asarray(hh[kk])
                                        for hh in hists])
                    for kk in hists[0]}
        mgr.save(k + 1, {
            "carry": carry, "hist": hist_acc,
            "stage_cpc": np.asarray(stage_cpc, np.float32),
            "cpc_rows": np.asarray(cpc_rows, np.float32)},
            metadata={"stage": k + 1, "steps": cfg.steps},
            blocking=True)
    hist = {kk: np.concatenate([np.asarray(hh[kk]) for hh in hists])
            for kk in hists[0]}
    hist["stage_cpc"] = np.asarray(stage_cpc, np.float32)
    return carry[0], hist, cpc_rows


_PROBLEM_ROW_FIELDS = tuple(f for f in TuneProblem._fields
                            if f != "prices")

# history keys that count events over rows merge across chunks/shards
# by summing; everything else (losses, taus, fractions) averages
_HIST_MERGE = {"guard_rejects": np.sum}


def _take_problem(problem: TuneProblem, idx: np.ndarray) -> TuneProblem:
    """Row-slice every [B] field of a `TuneProblem` (prices stay shared,
    exactly like `ScenarioGrid.take_rows`) — the generic shape-driven
    `repro.execution.take_rows`."""
    return take_rows(problem, idx, shared=("prices",))


@functools.cache
def _sharded_loop(n_dev: int, cfg: TuneConfig, telemetry: bool = False):
    """jit(shard_map(loop)) over a 1-D row mesh, cached per
    (n_dev, cfg, telemetry).

    Per-shard histories come back stacked [n_dev, steps]; the caller
    averages them (equal shard sizes)."""
    mesh = row_mesh(n_dev)
    rows = P("rows")

    def body(raw0, problem):
        raw_f, hist, cpc = _loop_body(raw0, problem, cfg,
                                      telemetry=telemetry)
        return raw_f, {k: v[None] for k, v in hist.items()}, cpc

    in_specs = (rows, TuneProblem(
        prices=P(), **{f: rows for f in _PROBLEM_ROW_FIELDS}))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(rows, rows, rows), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


@functools.cache
def _sharded_plan_loop(n_dev: int, cfg: TuneConfig, scale_rows: int,
                       with_dispatch: bool, telemetry: bool = False):
    """jit(shard_map(loop)) for an explicit ``mode='sharded'`` plan:
    the loop traces with ``axis_name='rows'`` so every fleet aggregate
    psum-reduces across shards — coupled objectives included. Cached
    per (n_dev, cfg, real-row count, dispatch-coupling presence)."""
    mesh = row_mesh(n_dev)
    rows = P("rows")

    def body(raw0, problem, coupling=None):
        raw_f, hist, cpc = _loop_body(raw0, problem, cfg, coupling,
                                      telemetry=telemetry,
                                      axis_name="rows",
                                      scale_rows=scale_rows)
        return raw_f, {k: v[None] for k, v in hist.items()}, cpc

    prob_specs = TuneProblem(
        prices=P(), **{f: rows for f in _PROBLEM_ROW_FIELDS})
    if with_dispatch:
        coup_specs = DispatchCoupling(
            cell_id=rows, prices=P(), keys=P(), order=P(), demand=P(),
            fixed=rows, power=rows, migrate_cost=P(), cpc_ref=P())
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(rows, prob_specs, coup_specs),
                           out_specs=(rows, rows, rows),
                           check_vma=False)
    else:
        fn = jax.shard_map(lambda r, p: body(r, p), mesh=mesh,
                           in_specs=(rows, prob_specs),
                           out_specs=(rows, rows, rows),
                           check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def _pad_rows(raw0: PolicyParams, problem: TuneProblem,
              coupling: Optional[DispatchCoupling], n_rows: int,
              b_pad: int):
    """Pad the row axis to ``b_pad`` for equal shard widths by
    repeating row 0 — *including* ``raw0``, so a warm start survives
    the padding (the silent-ignore bug this replaces). Padded rows are
    neutralised out of every fleet aggregate: zero site weight (power
    cap / up-hours), zero coupling power and fixed cost, and the dummy
    dispatch cell ``C`` that `soft_dispatch_ratio`'s sharded branch
    discards — their own tuning trajectory is real but dropped on
    return, and sum-reduction keeps them out of real rows' gradients.
    """
    pad = b_pad - n_rows

    def rep0(x):
        x = jnp.asarray(x)
        return jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)])

    raw0 = jax.tree.map(rep0, raw0)
    problem = problem._replace(
        **{f: rep0(getattr(problem, f)) for f in _PROBLEM_ROW_FIELDS})
    problem = problem._replace(
        site_weight=problem.site_weight.at[n_rows:].set(0.0))
    if coupling is not None:
        c = coupling.prices.shape[0]
        coupling = coupling._replace(
            cell_id=jnp.concatenate([
                coupling.cell_id, jnp.full((pad,), c, jnp.int32)]),
            fixed=rep0(coupling.fixed).at[n_rows:].set(0.0),
            power=rep0(coupling.power).at[n_rows:].set(0.0))
    return raw0, problem, coupling


def _run_sharded(raw0: PolicyParams, problem: TuneProblem,
                 cfg: TuneConfig, n_rows: int, n_dev: int,
                 coupling: Optional[DispatchCoupling],
                 telemetry: bool):
    """The explicit ``mode='sharded'`` path: pad rows to equal shard
    widths when needed (warm start carried through — see `_pad_rows`),
    run the psum-reduced loop, trim the padding off every per-row
    output."""
    width = -(-n_rows // n_dev)
    b_pad = width * n_dev
    if b_pad != n_rows:
        raw0, problem, coupling = _pad_rows(raw0, problem, coupling,
                                            n_rows, b_pad)
    fn = _sharded_plan_loop(n_dev, cfg, n_rows, coupling is not None,
                            telemetry)
    if coupling is not None:
        raw_f, hist, cpc = fn(raw0, problem, coupling)
    else:
        raw_f, hist, cpc = fn(raw0, problem)
    raw_f = jax.tree.map(lambda x: x[:n_rows], raw_f)
    return raw_f, {k: _HIST_MERGE.get(k, np.mean)(np.asarray(v), axis=0)
                   for k, v in hist.items()}, cpc[:n_rows]


def _run_loop(raw0: PolicyParams, problem: TuneProblem, cfg: TuneConfig,
              n_rows: int,
              coupling: Optional[DispatchCoupling] = None,
              telemetry: bool = False):
    """Dispatch the hot loop over the `ExecutionPlan` paths.

    Per-row math is identical in all of them (sum-reduction makes each
    row's gradient independent of its batch); chunking is bitwise, the
    sharded paths are ULP-equivalent — and since the psum rework an
    explicit ``mode='sharded'`` plan carries coupled objectives too
    (``mode='auto'`` stays conservative: coupling runs the single
    program unless sharding is asked for). Returns
    ``(raw_f, history, cpc_tuned)`` with history arrays [steps].
    """
    plan = cfg.resolved_plan
    rc = cfg.resolved_coupling
    coupled = coupling is not None or rc.power_cap_mw is not None \
        or rc.min_up_hours is not None
    # re-validated here because callers may hand `optimize` a plan
    # constructed outside TuneConfig's constructor invariant
    validate_plan_coupling(plan, rc, context="TuneConfig")

    chunk = plan.chunk_rows
    if chunk and n_rows > chunk:
        # pad to one compile shape by repeating row 0: padded rows are
        # tuned like any other and dropped afterwards — per-row math is
        # batch-independent, so the real rows are unaffected (the loss
        # *history*, a diagnostic, does average over the padding)
        raws, cpcs, hists = [], [], []
        for sl in row_chunks(n_rows, chunk):
            raw_j = jax.tree.map(lambda x: jnp.asarray(x)[sl], raw0)
            r, h, cp = tune_loop(raw_j, _take_problem(problem, sl),
                                 cfg=cfg, telemetry=telemetry)
            raws.append(r)
            hists.append(h)
            cpcs.append(cp)
        hist = {k: _HIST_MERGE.get(k, np.mean)(
                    [np.asarray(h[k]) for h in hists], axis=0)
                for k in hists[0]}
        return (concat_rows(raws, n_rows), hist,
                concat_rows(cpcs, n_rows))

    if plan.mode == "sharded":
        n_avail = len(jax.devices())
        cap = plan.devices if plan.devices else n_avail
        # >= 2 rows per shard always: width-1 shards scalarize on
        # XLA:CPU (observed 1-ulp drift) and are degenerate parallelism
        n_dev = max(1, min(cap, n_avail, n_rows // 2))
        if n_dev > 1:
            return _run_sharded(raw0, problem, cfg, n_rows, n_dev,
                                coupling, telemetry)
    elif plan.mode == "auto" and not coupled and not chunk:
        # auto-sharding: an explicit chunk_rows is a memory bound the
        # user asked for — it wins over auto-sharding even when the
        # grid is small enough to skip the chunked branch above (the
        # user opted into the bitwise chunk contract; shards are only
        # ULP-equivalent)
        n_avail = len(jax.devices())
        # largest divisor of B that keeps >= 2 rows per shard
        n_dev = next((d for d in range(min(n_avail, n_rows // 2), 0, -1)
                      if n_rows % d == 0), 1)
        if n_dev > 1:
            raw_f, hist, cpc = _sharded_loop(n_dev, cfg,
                                             telemetry)(raw0, problem)
            return raw_f, {k: _HIST_MERGE.get(k, np.mean)(
                               np.asarray(v), axis=0)
                           for k, v in hist.items()}, cpc

    raw_f, hist, cpc = tune_loop(raw0, problem, coupling, cfg=cfg,
                                 telemetry=telemetry)
    return raw_f, {k: np.asarray(v) for k, v in hist.items()}, cpc


def sharded_soft_objective(raw: PolicyParams, problem: TuneProblem, tau,
                           *, n_dev: int,
                           coupling: Optional[DispatchCoupling] = None,
                           **kwargs):
    """The global coupled loss evaluated under `shard_map` over a row
    mesh — the acceptance probe for the psum rework (and what
    `benchmarks/bench_tune_coupled.py` times).

    Each shard evaluates `soft_objective` with ``axis_name='rows'``
    (fleet aggregates psum-reduced, coupled term identical on every
    shard) and the global value is reassembled as
    ``psum(aux['base']) + aux['coupled']`` — the separable part summed
    across shards, the fleet-coupled part counted once. The result is
    ULP-equal to the single program's ``reduction='sum'`` loss, and its
    gradient w.r.t. ``raw`` is *exactly* the single program's (the
    coupled aggregates reduce through `psum_id`, whose backward is the
    identity). ``kwargs`` forward into `soft_objective` (power_cap_mw,
    min_up_hours, dispatch_blend, fused, ...). B must divide evenly
    into ``n_dev`` shards.

    Differentiable in ``raw`` only: a `custom_vjp` takes the gradient
    *inside* each shard's program (the same move `_sharded_plan_loop`
    makes), because reverse mode *through* `shard_map` stages the fused
    kernels' scalar residuals across the mesh, which spec inference
    rejects under ``check_vma=False`` — and ``check_vma=True`` hits the
    known scan replication-type bug. The per-shard adjoint IS the
    global one: each shard's gradient of its *local* loss
    ``base + coupled`` w.r.t. its own rows equals the single program's
    per-row gradient (cross-shard base terms don't touch these rows;
    the coupled term reduces through `psum_id`).
    ``problem``/``coupling``/``tau`` are treated as constants.
    """
    b = raw.raw_off.shape[0]
    if b % n_dev:
        raise ValueError(
            f"sharded_soft_objective: {b} rows do not split evenly over "
            f"{n_dev} shards — pad the batch (see _pad_rows) or pick a "
            "divisor shard count")
    mesh = row_mesh(n_dev)
    rows = P("rows")
    prob_specs = TuneProblem(
        prices=P(), **{f: rows for f in _PROBLEM_ROW_FIELDS})

    def body(raw_s, problem_s, coupling_s=None):
        _, aux = soft_objective(
            raw_s, problem_s, tau, dispatch=coupling_s,
            reduction="sum", axis_name="rows", scale_rows=b, **kwargs)
        # base is shard-local (separable sum), coupled is the full
        # global term on every shard — psum the first, keep the second
        return jax.lax.psum(aux["base"], "rows") + aux["coupled"]

    def grad_body(raw_s, problem_s, coupling_s=None):
        # differentiate the *local* loss (base + coupled), not the
        # psum-reassembled global value: other shards' base terms do
        # not depend on these rows, and the coupled term's cross-shard
        # aggregates go through psum_id, so the per-shard gradient of
        # the local loss IS the single program's per-row gradient
        def local(rs):
            return soft_objective(
                rs, problem_s, tau, dispatch=coupling_s,
                reduction="sum", axis_name="rows", scale_rows=b,
                **kwargs)[0]
        return jax.grad(local)(raw_s)

    if coupling is not None:
        in_specs = (rows, prob_specs, DispatchCoupling(
            cell_id=rows, prices=P(), keys=P(), order=P(), demand=P(),
            fixed=rows, power=rows, migrate_cost=P(), cpc_ref=P()))
        extra = (problem, coupling)
    else:
        in_specs = (rows, prob_specs)
        extra = (problem,)
    val_fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    grad_fn = jax.shard_map(grad_body, mesh=mesh, in_specs=in_specs,
                            out_specs=rows, check_vma=False)

    @jax.custom_vjp
    def loss(r):
        return val_fn(r, *extra)

    def loss_fwd(r):
        return val_fn(r, *extra), r

    def loss_bwd(r, ct):
        g = grad_fn(r, *extra)
        return (jax.tree.map(lambda x: x * ct, g),)

    loss.defvjp(loss_fwd, loss_bwd)
    return loss(raw)


def _hard_cpc_batched(p_on, p_off, off_level, problem: TuneProblem,
                      chunk_rows: int) -> np.ndarray:
    """`hard_cpc`, optionally evaluated in row chunks so the in-jit
    [B, T] price gather never exceeds the chunk footprint."""
    b = np.shape(p_on)[0]
    if not chunk_rows or b <= chunk_rows:
        return np.asarray(hard_cpc(p_on, p_off, off_level, problem),
                          np.float64)
    parts = [hard_cpc(jnp.asarray(p_on)[sl], jnp.asarray(p_off)[sl],
                      jnp.asarray(off_level)[sl],
                      _take_problem(problem, sl))
             for sl in row_chunks(b, chunk_rows)]
    return np.asarray(concat_rows(parts, b), np.float64)


def cell_best_rows(grid, cpc: np.ndarray) -> np.ndarray:
    """Index of the lowest-CPC row within each row's (market, system)
    cell, mapped back onto rows (robust to row permutations)."""
    key = cell_index(grid)
    best: dict[int, int] = {}
    for b in range(len(key)):
        c = int(key[b])
        if c not in best or cpc[b] < cpc[best[c]]:
            best[c] = b
    return np.asarray([best[int(c)] for c in key], np.int64)


def _dispatch_reeval(grid, params: PhysicalPolicy, cpc: np.ndarray,
                     best_row: np.ndarray, dcfg: DispatchConfig) -> dict:
    """Score the selected (tuned) and the best-swept policy sets under
    the *feasible* cross-site dispatcher — one site per (market, system)
    cell, hard constraints instead of the soft tuning penalties. A
    policy set that cannot meet the configured demand is not clipped to
    fit: it scores ``cpc = inf`` with the `DispatchInfeasible` reason
    recorded, and the feasible set (if any) is chosen."""
    key = cell_index(grid)
    sel: dict[int, int] = {}
    for b in range(len(key)):
        c = int(key[b])
        if c not in sel or cpc[b] < cpc[sel[c]]:
            sel[c] = b
    rows = np.asarray([sel[c] for c in sorted(sel)], np.int64)
    markets = np.asarray(grid.market_idx)[rows]
    prices = np.asarray(grid.prices)[markets]
    power = np.asarray(grid.power)[rows]
    fixed = np.asarray(grid.fixed)[rows]

    def run(p_on, p_off, lvl, take):
        try:
            return dispatch_solve(build_problem(
                prices, np.asarray(p_on)[take], np.asarray(p_off)[take],
                np.asarray(lvl)[take], power, dcfg, fixed=fixed),
                plan=getattr(dcfg, "plan", None)), None
        except DispatchInfeasible as e:
            return None, str(e)

    tuned, why_t = run(params.p_on, params.p_off, params.off_level, rows)
    sw = best_row[rows]
    swept, why_s = run(grid.p_on, grid.p_off, grid.off_level, sw)
    cpc_t = tuned.cpc if tuned is not None else float("inf")
    cpc_s = swept.cpc if swept is not None else float("inf")
    chosen = None if tuned is None and swept is None else \
        ("tuned" if cpc_t <= cpc_s else "swept")
    names = tuple(f"{grid.market_names[n]}/{grid.system_names[m]}"
                  for n, m in zip(np.asarray(grid.market_idx)[rows],
                                  np.asarray(grid.system_idx)[rows])) \
        if grid.market_names and grid.system_names else ()
    return {"cpc_tuned": cpc_t, "cpc_swept": cpc_s, "chosen": chosen,
            "tuned": tuned, "swept": swept, "rows": rows,
            "site_names": names,
            "infeasible_tuned": why_t, "infeasible_swept": why_s}


def optimize(grid, cfg: TuneConfig = TuneConfig(), *,
             warm_start=None) -> TuneResult:
    """Gradient-tune every scenario row of ``grid``; hard-re-evaluate.

    Each row is seeded at its own swept `PolicySpec` (so the grid's K
    policies double as K random restarts per (market, system) cell) and
    tuned for ``cfg.steps`` Adam steps under the annealed soft
    objective. ``warm_start`` overrides the seed: a `PolicyParams` (raw),
    a `PhysicalPolicy` (mapped through `inverse_transform`), or a prior
    `TuneResult` (its ``.raw``) — the entry point a receding-horizon
    caller (`repro.live`, `examples/live_operator.py`) uses to re-tune
    at each cadence tick from the previous tick's solution with a short
    ``cfg.steps`` budget instead of a cold anneal. The final selection keeps, per row, the best hard-CPC
    policy among the tuned parameters and the swept baselines — when
    hardware parameters (idle draw, restart costs) are uniform within a
    cell, the reported ``cpc`` therefore matches or beats the best swept
    policy on every row. With fleet-coupling penalties configured the
    swept fallback is disabled (swept policies ignore the constraints),
    so ``cpc`` reports the tuned params unconditionally — an explicit
    ``chunk_rows`` raises, since coupled terms see every row at once,
    and auto-mode sharding stays off; an explicit
    ``plan=ExecutionPlan(mode='sharded')`` *does* shard the coupled
    objective, psum-reducing its fleet aggregates across the row mesh.

    With ``cfg.dispatch_soft`` the annealed objective additionally
    differentiates through the relaxed water-fill dispatcher
    (`repro.tune.objective.soft_dispatch_ratio`), the per-row swept
    fallback is disabled for the same reason as above, and the final
    policy set is re-scored on *feasible* `repro.dispatch.dispatch`
    (under ``cfg.dispatch`` if also given, else under the same config)
    against the best-swept set — so the reported fleet CPC under hard
    dispatch is never worse than the swept baseline's.

    With a `repro.workload.Workload` (``cfg.workload``, defaulting to
    ``grid.workload``) the annealed objective adds the soft work-ledger
    term (`soft_objective`'s ``workload`` kwarg) and the final per-row
    selection is judged by *realized workload cost* — energy + SLO
    deferral + VoLL drops on one shared demand sample
    (`repro.workload.realized_cost`) — instead of bare CPC, landing in
    ``TuneResult.workload_cost``; the selected policy never costs more
    than the best swept policy of its cell under the same workload.
    """
    telemetry = obs.enabled()
    problem = problem_from_grid(grid)
    if warm_start is None:
        raw0 = init_from_grid(grid)
    elif isinstance(warm_start, TuneResult):
        raw0 = warm_start.raw
    elif isinstance(warm_start, PhysicalPolicy):
        raw0 = inverse_transform(warm_start)
    elif isinstance(warm_start, PolicyParams):
        raw0 = warm_start
    else:
        raise TypeError("warm_start must be PolicyParams, PhysicalPolicy "
                        f"or TuneResult, got {type(warm_start).__name__}")
    if np.asarray(raw0.raw_off).shape != (grid.n_rows,):
        raise ValueError(
            f"warm_start has {np.asarray(raw0.raw_off).shape} raw_off for "
            f"a {grid.n_rows}-row grid")
    if warm_start is not None:
        # the tuning loop donates its parameter carry; copy so the
        # caller's warm-start source (e.g. the previous tick's
        # TuneResult in a receding-horizon loop) stays alive
        raw0 = PolicyParams(*(jnp.array(a) for a in raw0))
    rc = cfg.resolved_coupling
    chunk = cfg.resolved_plan.chunk_rows
    wl = cfg.workload if cfg.workload is not None \
        else getattr(grid, "workload", None)
    if wl is not None and cfg.workload is None:
        # a grid-carried Workload flows into the loop too (cfg is the
        # jit-static carrier `_make_step` reads)
        cfg = cfg._replace(workload=wl)
    coupling = dispatch_coupling_from_grid(grid, rc.dispatch) \
        if rc.dispatch is not None else None
    raw_f, hist, cpc_tuned_dev = _run_loop(raw0, problem, cfg,
                                           grid.n_rows, coupling,
                                           telemetry)
    stage_cpc = np.asarray(hist.pop("stage_cpc"), np.float64)
    cpc_tuned = np.asarray(cpc_tuned_dev, np.float64)

    # hard re-evaluation of the swept baselines at tau -> 0 (chunked
    # under the same memory bound the tuning run declared)
    swept_plan = ExecutionPlan(mode="chunked", chunk_rows=chunk,
                               contract="bitwise") if chunk \
        else ExecutionPlan(mode="single")
    swept = backtest(grid, use_pallas=False, plan=swept_plan)
    cpc_swept = np.asarray(swept.cpc, np.float64)

    tuned = transform(raw_f)
    wl_demand = None
    wc_tuned = wc_swept = None
    if wl is not None:
        # the hard selection yardstick becomes the *realized* workload
        # cost — energy + SLO deferral + VoLL drops — on one shared
        # demand sample, so the tuned/swept comparison is paired and
        # the selected policy can never cost more than the best swept
        # one under the same workload
        from repro.workload import realized_cost
        wl_demand = wl.sample_demand_mw(grid.n_hours)
        wc_tuned = np.asarray(realized_cost(
            grid, tuned.p_on, tuned.p_off, tuned.off_level, wl,
            demand_mw=wl_demand), np.float64)
        wc_swept = np.asarray(realized_cost(
            grid, grid.p_on, grid.p_off, grid.off_level, wl,
            demand_mw=wl_demand), np.float64)
        best_row = cell_best_rows(grid, wc_swept)
    else:
        best_row = cell_best_rows(grid, cpc_swept)
    cpc_swept_best = cpc_swept[best_row]

    # cell-best swept params evaluated under *this* row's hardware
    cb = PhysicalPolicy(p_on=grid.p_on[best_row], p_off=grid.p_off[best_row],
                        off_level=grid.off_level[best_row])
    cpc_cb = _hard_cpc_batched(cb.p_on, cb.p_off, cb.off_level, problem,
                               chunk)

    cand = np.stack([cpc_tuned, cpc_swept, cpc_cb])        # [3, B]
    # the selection yardstick: realized workload cost when a Workload
    # is configured, bare hard CPC otherwise
    yard = np.stack([wc_tuned, wc_swept, wc_swept[best_row]]) \
        if wl is not None else cand
    if rc.binds:
        # fleet-coupling constraints: the swept baselines ignore them, so
        # falling back to a lower-CPC swept policy would silently violate
        # the constraint the user asked for — keep the tuned params.
        # (Dispatch-aware runs likewise: a per-row swept fallback judged
        # on *isolated* CPC would undo the fleet-role specialisation the
        # dispatch term just taught; the swept set still competes, as a
        # whole fleet, in the hard dispatch re-scoring below.)
        source = np.zeros(cand.shape[1], np.int64)
    else:
        source = np.argmin(yard, axis=0)
    cpc = cand[source, np.arange(cand.shape[1])]
    workload_cost = yard[source, np.arange(yard.shape[1])] \
        if wl is not None else None

    def pick(tuned_v, own_v, cb_v):
        stacked = jnp.stack([jnp.asarray(tuned_v), jnp.asarray(own_v),
                             jnp.asarray(cb_v)])
        return stacked[source, jnp.arange(stacked.shape[1])]

    params = PhysicalPolicy(
        p_on=pick(tuned.p_on, grid.p_on, cb.p_on),
        p_off=pick(tuned.p_off, grid.p_off, cb.p_off),
        off_level=pick(tuned.off_level, grid.off_level, cb.off_level))

    dispatch_out = None
    reeval_cfg = rc.reeval_config
    if reeval_cfg is not None:
        if rc.relief_config is not None and reeval_cfg.relief is None:
            # a Coupling-level relief covers the hard re-scoring too:
            # a storm-degraded policy set sheds at VoLL instead of
            # scoring a bare `inf`
            reeval_cfg = reeval_cfg._replace(relief=rc.relief_config)
        dispatch_out = _dispatch_reeval(grid, params, cpc, best_row,
                                        reeval_cfg)

    result = TuneResult(
        params=params, raw=raw_f, cpc=cpc, cpc_tuned=cpc_tuned,
        cpc_swept=cpc_swept, cpc_swept_best=cpc_swept_best,
        improvement_vs_best=1.0 - cpc / cpc_swept_best,
        improvement_vs_own=1.0 - cpc / cpc_swept,
        source=source, history=hist, stage_cpc=stage_cpc,
        dispatch=dispatch_out,
        guard_count=int(np.sum(hist.get("guard_rejects", 0.0))),
        workload_cost=workload_cost)
    if telemetry:
        _emit_tune_events(cfg, result)
    return result


def _emit_tune_events(cfg: TuneConfig, res: TuneResult) -> None:
    """Stream the finished run's history into the trace: one
    ``tune.step`` per optimization step (loss / tau / penalty, plus
    grad-norm and clip-fraction — present because the loop ran with its
    telemetry side-outputs), one ``tune.stage`` per hard re-eval
    boundary, one ``tune.result``."""
    hist = res.history
    step_keys = [k for k in ("loss", "tau", "penalty", "dispatch_ratio",
                             "grad_norm", "clip_frac") if k in hist]
    for i in range(len(hist["loss"])):
        obs.trace_event("tune.step",
                        {"step": i,
                         **{k: float(hist[k][i]) for k in step_keys}})
        if "grad_norm" in hist:
            obs.histogram("tune.grad_norm").observe(hist["grad_norm"][i])
    bounds = _stage_bounds(cfg)
    for k, v in enumerate(res.stage_cpc):
        obs.trace_event("tune.stage", {"stage": k,
                                       "through_step": bounds[k + 1],
                                       "cpc_hard_mean": float(v)})
    src_names = ("tuned", "own_swept", "cell_best")
    obs.trace_event("tune.result", {
        "rows": int(res.cpc.shape[0]), "steps": cfg.steps,
        "cpc_mean": float(np.mean(res.cpc)),
        "cpc_tuned_mean": float(np.mean(res.cpc_tuned)),
        "cpc_swept_best_mean": float(np.mean(res.cpc_swept_best)),
        "improvement_vs_best_mean": float(np.mean(res.improvement_vs_best)),
        "source_counts": {src_names[s]: int(n) for s, n in
                          zip(*np.unique(res.source, return_counts=True))}})
    if res.guard_count:
        rej = np.asarray(res.history["guard_rejects"])
        obs.trace_event("tune.guard", {
            "rejects_total": int(res.guard_count),
            "steps_affected": int((rej > 0).sum()),
            "first_step": int(np.argmax(rej > 0)),
            "rows": int(res.cpc.shape[0])})
        obs.counter("tune.guard_rejects").inc(int(res.guard_count))
    obs.gauge("tune.cpc_mean").set(float(np.mean(res.cpc)))
    obs.counter("tune.runs").inc()
