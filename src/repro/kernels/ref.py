"""Pure-jnp oracles for every Pallas kernel (the `ref.py` of kernels/).

These are *direct* (non-blocked) implementations — O(S^2) score tensors,
materialised state sequences — used only by tests and never by the model
(the model's own XLA path is the separately-implemented blockwise form in
`repro.models.attention` / `repro.models.ssm`, giving three independent
implementations that must agree).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


class FleetScanOut(NamedTuple):
    """Per-row sufficient statistics of a batched policy backtest.

    All cost quantities downstream (CPC, TCO, reduction) are affine in
    these four sums, so the scan never materialises the [B, T] mask.
    """

    draw_price_sum: jax.Array   # sum_t draw_t * p_t            [B]
    up_units: jax.Array         # sum_t capacity_t               [B]
    n_starts: jax.Array         # number of off->on transitions  [B]
    restart_price_sum: jax.Array  # sum_t start_t * p_t          [B]


def hard_hour_step(on_prev, p_t, p_on, p_off, off_level, idle_frac):
    """One hour of the hard shutdown state machine — the single source
    of the per-hour update, shared (elementwise, broadcasting) by
    `fleet_scan_ref` and the telemetry companion `fleet_hourly_ref` so
    the per-hour records aggregate exactly the trajectory the backtest
    scores. Returns ``(on, start, cap, draw)``."""
    on = jnp.where(p_t > p_off, 0.0,
                   jnp.where(p_t <= p_on, 1.0, on_prev))
    start = jnp.maximum(on - on_prev, 0.0)
    cap = off_level + (1.0 - off_level) * on
    draw = cap + idle_frac * (1.0 - cap)
    return on, start, cap, draw


class FleetHourly(NamedTuple):
    """Per-hour fleet aggregates ([T] each) of a batched backtest — the
    payload of the ``fleet.hourly`` telemetry drain. Reductions run
    on-device inside the scan, so only 4T floats ever cross to the
    host."""

    on_mw: jax.Array       # sum_b weight_b * cap_bt (weighted capacity)
    draw_price: jax.Array  # sum_b weight_b * draw_bt * p_bt (EUR-rate)
    starts: jax.Array      # off->on transitions across rows
    stops: jax.Array       # on->off transitions across rows


def fleet_hourly_ref(prices: jax.Array, p_on: jax.Array, p_off: jax.Array,
                     off_level: jax.Array, idle_frac: jax.Array,
                     weight: jax.Array) -> FleetHourly:
    """Hour-indexed companion of `fleet_scan_ref`: same state machine
    (via `hard_hour_step`), but emitting [T]-shaped fleet aggregates
    instead of per-row sums. ``weight`` ([B], e.g. each row's MW rating)
    scales capacity and draw into fleet-level MW; transition counts are
    unweighted."""
    p = jnp.asarray(prices, jnp.float32)
    b = p.shape[0]
    p_on, p_off, off_level, idle_frac, weight = (
        jnp.broadcast_to(jnp.asarray(v, jnp.float32), (b,))
        for v in (p_on, p_off, off_level, idle_frac, weight))

    def step(on_prev, p_t):
        on, start, cap, draw = hard_hour_step(on_prev, p_t, p_on, p_off,
                                              off_level, idle_frac)
        stop = jnp.maximum(on_prev - on, 0.0)
        ys = (jnp.sum(weight * cap), jnp.sum(weight * draw * p_t),
              jnp.sum(start), jnp.sum(stop))
        return on, ys

    _, ys = jax.lax.scan(step, jnp.ones((b,), jnp.float32), p.T)
    return FleetHourly(*ys)


def fleet_scan_ref(prices: jax.Array, p_on: jax.Array, p_off: jax.Array,
                   off_level: jax.Array, idle_frac: jax.Array
                   ) -> FleetScanOut:
    """Sequential oracle for the batched hysteresis/threshold scan.

    prices: [B, T]; p_on/p_off/off_level/idle_frac: [B] per-row policy
    parameters (p_on <= p_off; p_on == p_off is a plain threshold).

    State machine per row (initial state: on, matching
    `repro.core.policy.hysteresis_policy`'s initial carry):

        on_t = 0 if p_t > p_off, 1 if p_t <= p_on, else on_{t-1}

    With p_on == p_off the hold-band is empty and this is *exactly*
    `repro.core.policy.threshold_policy` (run while p <= threshold); note
    `hysteresis_policy` resumes on strict p < p_on instead, so the two
    differ only at samples exactly equal to p_on.
    Capacity while "off" is ``off_level`` (partial shutdown, paper §V-C);
    residual draw while off is ``idle_frac`` of the *shut-down* capacity.
    """
    p = jnp.asarray(prices, jnp.float32)
    b = p.shape[0]
    p_on, p_off, off_level, idle_frac = (
        jnp.broadcast_to(jnp.asarray(v, jnp.float32), (b,))
        for v in (p_on, p_off, off_level, idle_frac))

    def step(carry, p_t):
        on_prev, acc = carry
        on, start, cap, draw = hard_hour_step(on_prev, p_t, p_on, p_off,
                                              off_level, idle_frac)
        acc = (acc[0] + draw * p_t, acc[1] + cap,
               acc[2] + start, acc[3] + start * p_t)
        return (on, acc), None

    zeros = jnp.zeros((b,), jnp.float32)
    init = (jnp.ones((b,), jnp.float32), (zeros, zeros, zeros, zeros))
    (_, acc), _ = jax.lax.scan(step, init, p.T)
    return FleetScanOut(*acc)


def queue_scan_ref(arrivals: jax.Array, cap: jax.Array, *,
                   deadline: int, bound) -> tuple[jax.Array, jax.Array,
                                                  jax.Array, jax.Array]:
    """Sequential oracle for the hard work-ledger scan
    (`repro.kernels.queue_scan.queue_scan`).

    arrivals/cap: [R, T] MWh per hour. Deliberately a different
    formulation from the kernel's parallel-cumsum fill: the age buckets
    are walked *sequentially* (python-unrolled — ``deadline`` is
    static), serving oldest-first from a running remaining-capacity
    variable and re-queueing under ``bound`` with a running kept-mass —
    the greedy prose the cumsum idiom must reproduce. Returns per-hour
    ``(served [R, T], dropped [R, T], backlog [R, T], q_final [R, D])``.
    """
    a = jnp.asarray(arrivals)
    dtype = a.dtype if jnp.issubdtype(a.dtype, jnp.floating) else jnp.float32
    a = a.astype(dtype)
    c = jnp.broadcast_to(jnp.asarray(cap, dtype), a.shape)
    r = a.shape[0]
    d = int(deadline)

    def hour(q, inp):
        a_t, c_t = inp
        # q[:, i] has waited i+1 hours; serve oldest first
        work = [q[:, d - 1 - i] for i in range(d)] + [a_t]
        rem = c_t
        served = jnp.zeros_like(c_t)
        unserved = []
        for w in work:
            s_i = jnp.minimum(rem, w)
            rem = rem - s_i
            served = served + s_i
            unserved.append(w - s_i)
        dropped = unserved[0]             # waited past the deadline
        kept = jnp.zeros_like(c_t)
        new_q = []
        for w in unserved[1:]:            # oldest survivor first
            keep = jnp.minimum(w, jnp.maximum(bound - kept, 0.0))
            kept = kept + keep
            dropped = dropped + (w - keep)
            new_q.append(keep)
        q = jnp.stack(new_q[::-1], axis=1) if d \
            else jnp.zeros((r, 0), dtype)
        return q, (served, dropped, kept)

    q0 = jnp.zeros((r, d), dtype)
    q_final, (served, dropped, backlog) = jax.lax.scan(
        hour, q0, (a.T, c.T))
    return served.T, dropped.T, backlog.T, q_final


def soft_gates(p_t, p_on, p_off, inv_tau):
    """Per-hour sigmoid event gates of the relaxed hysteresis recurrence.

    Returns ``(a, f, alpha, beta)`` with a = sigmoid((p_on - p_t)/tau)
    ("turn on"), f = sigmoid((p_t - p_off)/tau) ("turn off"), and the
    affine-map coefficients of s_t = alpha_t s_{t-1} + beta_t. Shared
    verbatim — elementwise, broadcasting — by `soft_scan_ref`,
    `repro.kernels.soft_scan.soft_state`, and both paths of the fused
    VJP (`repro.kernels.soft_scan_vjp`), so every implementation relaxes
    the state machine with the *same* per-hour math.
    """
    a = jax.nn.sigmoid((p_on - p_t) * inv_tau)
    f = jax.nn.sigmoid((p_t - p_off) * inv_tau)
    return a, f, (1.0 - a) * (1.0 - f), a


def soft_gate_grad(p_t, s_prev, u_t, p_on, p_off, inv_tau, gates=None):
    """Per-hour chain rule of the relaxed recurrence.

    Given the adjoint u_t = dL/ds_t (fully accumulated through later
    hours) and the entering state s_{t-1}, backpropagates through
    s_t = alpha_t s_{t-1} + beta_t and the gates to the hour's inputs.
    Returns per-hour contributions ``(d_p, d_p_on, d_p_off, d_inv_tau)``
    — callers sum the last three over t (and convert d_inv_tau to d_tau
    via dtau = -inv_tau^2 d_invtau). Shared verbatim by the sequential
    oracle `soft_scan_grad_ref`, the blocked XLA backward, and the
    Pallas backward kernel, exactly like `dispatch_alloc_hour`.
    ``gates`` lets a caller that already evaluated `soft_gates` (the
    blocked backwards need alpha for the adjoint recurrence anyway)
    pass ``(a, f)`` instead of paying the sigmoids twice.
    """
    a, f = gates if gates is not None else \
        soft_gates(p_t, p_on, p_off, inv_tau)[:2]
    d_alpha = u_t * s_prev                  # d beta = u_t
    d_a = u_t - d_alpha * (1.0 - f)         # alpha = (1-a)(1-f), beta = a
    d_f = -d_alpha * (1.0 - a)
    d_zon = d_a * a * (1.0 - a)             # z_on  = (p_on - p) inv_tau
    d_zoff = d_f * f * (1.0 - f)            # z_off = (p - p_off) inv_tau
    d_p = (d_zoff - d_zon) * inv_tau
    d_p_on = d_zon * inv_tau
    d_p_off = -d_zoff * inv_tau
    d_inv_tau = d_zon * (p_on - p_t) + d_zoff * (p_t - p_off)
    return d_p, d_p_on, d_p_off, d_inv_tau


def soft_scan_grad_ref(prices: jax.Array, p_on: jax.Array,
                       p_off: jax.Array, g: jax.Array, *, tau
                       ) -> tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array]:
    """Sequential oracle for the VJP of the soft-state trajectory.

    Given the cotangent ``g`` [B, T] of `soft_scan.soft_state`'s output,
    runs the recurrence forward (materialising the state sequence — this
    is an oracle, not a fast path), then walks the time grid in reverse
    accumulating the adjoint u_t = g_t + alpha_{t+1} u_{t+1} and the
    per-hour input gradients via `soft_gate_grad`. Returns
    ``(d_prices [B, T], d_p_on [B], d_p_off [B], d_tau [])``.
    """
    p = jnp.asarray(prices)
    dtype = p.dtype if jnp.issubdtype(p.dtype, jnp.floating) else jnp.float32
    p = p.astype(dtype)
    b = p.shape[0]
    p_on = jnp.broadcast_to(jnp.asarray(p_on, dtype), (b,))
    p_off = jnp.broadcast_to(jnp.asarray(p_off, dtype), (b,))
    g = jnp.asarray(g, dtype)
    inv_tau = 1.0 / jnp.asarray(tau, dtype)

    def fwd(s_prev, p_t):
        _, _, alpha, beta = soft_gates(p_t, p_on, p_off, inv_tau)
        return alpha * s_prev + beta, (s_prev, alpha)

    _, (s_prev_t, alpha_t) = jax.lax.scan(fwd, jnp.ones((b,), dtype), p.T)

    def bwd(carry, inp):
        u_next, alpha_next = carry          # u_{t+1}, alpha_{t+1}
        p_t, g_t, s_prev, alpha = inp
        u = g_t + alpha_next * u_next
        d_p, d_on, d_off, d_it = soft_gate_grad(p_t, s_prev, u, p_on,
                                                p_off, inv_tau)
        return (u, alpha), (d_p, d_on, d_off, d_it)

    zeros = jnp.zeros((b,), dtype)
    _, (d_p, d_on, d_off, d_it) = jax.lax.scan(
        bwd, (zeros, zeros), (p.T, g.T, s_prev_t, alpha_t), reverse=True)
    d_tau = -inv_tau ** 2 * jnp.sum(d_it)
    return d_p.T, jnp.sum(d_on, axis=0), jnp.sum(d_off, axis=0), d_tau


def soft_scan_ref(prices: jax.Array, p_on: jax.Array, p_off: jax.Array,
                  off_level: jax.Array, idle_frac: jax.Array, *,
                  tau: float) -> FleetScanOut:
    """Sequential oracle for the temperature-``tau`` relaxation of
    `fleet_scan_ref` (see `repro.kernels.soft_scan` for the fused form).

    The hard two-threshold state machine

        on_t = 0 if p_t > p_off, 1 if p_t <= p_on, else on_{t-1}

    is relaxed with sigmoid event gates a_t = sigmoid((p_on - p_t)/tau)
    ("turn on") and b_t = sigmoid((p_t - p_off)/tau) ("turn off"):

        s_t = a_t + (1 - a_t)(1 - b_t) s_{t-1},   s_{-1} = 1

    which is affine in s_{t-1} and recovers the hard recurrence (with the
    kernel's on-wins precedence) as tau -> 0 at every sample not exactly
    on a threshold. Restarts are counted softly as s_t (1 - s_{t-1}) —
    smooth everywhere, and equal to the hard 0->1 indicator on binary
    states. Everything is differentiable in (p_on, p_off, off_level,
    idle_frac, prices); computation runs in the price dtype (float64
    under x64 — finite-difference gradient checks rely on this).
    """
    p = jnp.asarray(prices)
    dtype = p.dtype if jnp.issubdtype(p.dtype, jnp.floating) else jnp.float32
    p = p.astype(dtype)
    b = p.shape[0]
    p_on, p_off, off_level, idle_frac = (
        jnp.broadcast_to(jnp.asarray(v, dtype), (b,))
        for v in (p_on, p_off, off_level, idle_frac))
    inv_tau = 1.0 / jnp.asarray(tau, dtype)

    def step(carry, p_t):
        s_prev, acc = carry
        _, _, alpha, beta = soft_gates(p_t, p_on, p_off, inv_tau)
        s = alpha * s_prev + beta
        start = s * (1.0 - s_prev)
        cap = off_level + (1.0 - off_level) * s
        draw = cap + idle_frac * (1.0 - cap)
        acc = (acc[0] + draw * p_t, acc[1] + cap,
               acc[2] + start, acc[3] + start * p_t)
        return (s, acc), None

    zeros = jnp.zeros((b,), dtype)
    init = (jnp.ones((b,), dtype), (zeros, zeros, zeros, zeros))
    (_, acc), _ = jax.lax.scan(step, init, p.T)
    return FleetScanOut(*acc)


def _row(v) -> jax.Array:
    """[n] (or [1, n]) -> [1, n]. The per-hour dispatch math runs on rows:
    one hour as the Pallas kernels read it (one sublane, n lanes), so the
    oracle and the kernels evaluate the same ops on the same shapes."""
    return jnp.reshape(jnp.asarray(v), (1, -1))


def _gather_lanes(x: jax.Array, idx: jax.Array, onehot: bool) -> jax.Array:
    """``x[0, idx[0]]`` for rows x [1, n] of finite values and int idx
    [1, m]. ``onehot`` (the Pallas kernels: Mosaic lowers no 1-D gather)
    computes it as a one-hot matmul: every output sums exactly one
    nonzero product (the rest are x_k * 0), so it equals the plain gather
    bit for bit in any summation order; HIGHEST keeps f32 operands
    unrounded on the MXU. The matmul costs O(n m) per call, so the XLA
    paths take the O(m) gather."""
    if not onehot:
        return jnp.take_along_axis(x, idx, axis=-1)
    k = jax.lax.broadcasted_iota(jnp.int32, (x.shape[-1], idx.shape[-1]), 0)
    onehot = (idx == k).astype(x.dtype)
    return jnp.dot(x, onehot, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=x.dtype)


def doubling_prefix(x: jax.Array, op=jnp.add, fill=0, axis: int = -1
                    ) -> jax.Array:
    """Inclusive prefix ``op`` (add or maximum) along ``axis`` by
    log-depth doubling, ``fill`` shifted in past the edge: a fixed
    association of plain elementwise ops, the same on every backend.
    Mosaic lowers no `cumsum` or `cummax`, so the kernels scan with
    this."""
    axis = axis % x.ndim
    n = x.shape[axis]
    d = 1
    while d < n:
        pad = jnp.full(x.shape[:axis] + (d,) + x.shape[axis + 1:], fill,
                       x.dtype)
        x = op(x, jnp.concatenate(
            [pad, jax.lax.slice_in_dim(x, 0, n - d, axis=axis)], axis=axis))
        d *= 2
    return x


def dispatch_alloc_hour(prev: jax.Array, dwell: jax.Array,
                        avail: jax.Array, order: jax.Array,
                        rank: jax.Array, demand, *, min_dwell: int,
                        onehot: bool = False
                        ) -> tuple[jax.Array, jax.Array]:
    """One hour of feasible cross-site dispatch (greedy water-fill).

    Shared *verbatim* by `dispatch_ref` and the Pallas kernel
    (`repro.kernels.dispatch_scan`), so the two paths produce
    bit-identical allocations — only the orchestration around this
    function (lax.scan vs time-blocked grid with VMEM carry) differs.

    Each site contributes three price-sorted segments of capacity:

      locked  — load held < ``min_dwell`` hours; ranked below every
                other segment (price-ordered among themselves) so it is
                retained unless demand itself shrinks below the locks
      retain  — the rest of the previous allocation, priced at
                p - migrate_cost (leaving must pay the migration fee)
      fresh   — unused capacity at the plain market price

    ``order``/``rank`` are the ascending sort permutation of the 3S
    segment keys and its inverse, precomputed on the host
    (`repro.dispatch.segment_rank`): keys depend only on prices and the
    migration premium, never on the running state. The greedy fill is
    then sort-free — gather the widths into price order, one exclusive
    prefix sum, gather each segment's cheaper-mass back, and take
    ``clip(demand - cheaper_mass, 0, width)``. The prefix sum is a fixed
    doubling (`doubling_prefix`) and the gathers exact (`_gather_lanes`):
    one-hot matmuls with ``onehot``, which the Pallas kernels pass
    because the TPU kernel compiler lowers no 1-D gather, plain gathers
    otherwise. Both forms give the same bits.

    prev/dwell/avail: [S] or [1, S]; order/rank: [3S] or [1, 3S] int;
    demand: MW, scalar or [1, 1]. Returns ``(alloc, dwell')`` shaped
    like ``prev``.
    Capacity loss breaks a dwell lock (physics beats contract): locked
    width is capped at ``avail``.
    """
    shape = jnp.shape(prev)
    prev, dwell, avail, order, rank, demand = map(
        _row, (prev, dwell, avail, order, rank, demand))
    s = prev.shape[-1]
    held = jnp.minimum(prev, avail)
    if min_dwell > 0:
        locked = jnp.where(dwell > 0.0, held, 0.0)
    else:
        locked = jnp.zeros_like(held)
    widths = jnp.concatenate([locked, held - locked, avail - held], axis=-1)
    sorted_w = _gather_lanes(widths, order, onehot)
    excl = doubling_prefix(sorted_w) - sorted_w
    before = _gather_lanes(excl, rank, onehot)   # MW at cheaper segments
    fill = jnp.clip(demand - before, 0.0, widths)
    alloc = fill[:, :s] + fill[:, s:2 * s] + fill[:, 2 * s:]
    if min_dwell > 0:
        dwell = jnp.where(alloc > prev + DWELL_EVENT_MW, float(min_dwell),
                          jnp.maximum(dwell - 1.0, 0.0))
    return alloc.reshape(shape), dwell.reshape(shape)


def dispatch_ref(avail: jax.Array, order: jax.Array, rank: jax.Array,
                 demand: jax.Array, *, min_dwell: int = 0) -> jax.Array:
    """Sequential oracle for the hour-by-hour fleet dispatch scan.

    avail: [S, T] available MW per site (policy on/off state x site
    rating); order/rank: [T, 3S] precomputed segment sort data;
    demand: [T] MW. Returns the allocation [S, T]. Initial state is
    empty (hour 0 *places* the fleet's load, which is not counted as
    migration by the accounting in `repro.dispatch`).
    """
    a = jnp.asarray(avail, jnp.float32)
    s = a.shape[0]

    def step(carry, inp):
        prev, dwell = carry
        a_t, o_t, r_t, d_t = inp
        alloc, dwell = dispatch_alloc_hour(prev, dwell, a_t, o_t, r_t,
                                           d_t, min_dwell=min_dwell)
        return (alloc, dwell), alloc

    zeros = jnp.zeros((s,), jnp.float32)
    _, alloc_t = jax.lax.scan(
        step, (zeros, zeros),
        (a.T, jnp.asarray(order, jnp.int32), jnp.asarray(rank, jnp.int32),
         jnp.asarray(demand, jnp.float32)))
    return alloc_t.T


DWELL_EVENT_MW = 1e-3  # allocation increase (MW) that counts as a
                       # fresh placement and rearms the dwell lock.
                       # Shared by the hard fill and its soft
                       # relaxation: 1 kW is far above both paths' f32
                       # rounding (so a site whose load merely *rounds*
                       # differently never rearms) and far below any
                       # real cross-site move, which is what lets the
                       # soft dwell dynamics converge to the hard ones
                       # as tau -> 0 instead of flipping locks on noise.

_WL_TINY = 1e-30      # absolute floor for water-level denominators
_WL_SIGMA_SPAN = 40.0  # sigmoid(±40) saturates in f32 *and* f64: the
                       # soft water level lives within ±40 tau of the
                       # hard one (see `soft_water_level`)
_DWELL_CNT_SCALE = 0.05  # dwell-count temperature per price-unit tau:
                         # the hard countdown parks the counter exactly
                         # on the min(d, 1) / relu(d - 1) kinks, so the
                         # soft path smooths both at tau_cnt =
                         # tau * this (sigmoid lock gate, softplus
                         # decrement) — co-annealed, FD-checkable
                         # gradients at every tau, hard counters in the
                         # limit


def soft_water_level(keys: jax.Array, widths: jax.Array, demand,
                     lam0, inv_tau, *, n_bisect: int = 30) -> jax.Array:
    """Level ``lam`` of the entropic water-fill: the root of

        f(lam) = sum_j widths_j sigmoid((lam - keys_j) / tau) = demand

    f is monotone in lam, so the root is unique whenever it exists;
    ``lam0`` must be the *hard* water level (the marginal segment's key
    from the precomputed sort), which brackets the soft root within
    ``±40 tau`` (sigmoid(40) == 1 in f32: every segment cheaper than the
    hard level is full at lam0 + 40 tau, so f covers the demand there,
    and only the below-marginal mass — at most the demand — survives at
    lam0 - 40 tau). Fixed-count bisection under ``stop_gradient`` finds
    the root; one *differentiable* Newton step from the stop-gradded
    solution then supplies the exact first-order implicit gradient
    (d lam = (d demand - sum_j sigma_j d w_j - ...) / f'(lam)) without
    backpropagating through the solver iterations. The correction is
    clipped to the bracket radius so an infeasible hour (demand above
    total width: f' -> 0 at the saturated bracket edge) degrades to
    "everything full" instead of emitting huge levels; callers
    renormalise the fill mass, so the clip never distorts feasible
    hours, where the correction is O(bracket / 2^n_bisect).

    keys/widths: [n] or [1, n]; the level comes back shaped like
    ``demand``.
    """
    shape = jnp.shape(demand)
    keys, widths, demand, lam0 = map(_row, (keys, widths, demand, lam0))
    lam_hat = _bisect_level(keys, widths, demand, lam0, inv_tau,
                            n_bisect=n_bisect)
    return soft_water_level_fixed(keys, widths, demand, lam_hat,
                                  inv_tau).reshape(shape)


def _lane_sum(x: jax.Array) -> jax.Array:
    """Sum of a row [1, n] as [1, 1]: per-hour scalars stay vectors, the
    form the kernels hold them in."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _bisect_level(keys: jax.Array, widths: jax.Array, demand, lam0,
                  inv_tau, *, n_bisect: int = 30) -> jax.Array:
    """The non-differentiable half of `soft_water_level`: fixed-count
    bisection from the hard-level bracket, returned under
    ``stop_gradient``. Saved as a per-hour residual by the fused
    dispatch VJP (`repro.kernels.soft_dispatch`) so the backward pass
    never re-runs the solver. keys/widths: [1, n] rows; demand and
    lam0: [1, 1]."""
    span = _WL_SIGMA_SPAN / inv_tau

    def f(lam):
        return _lane_sum(widths * jax.nn.sigmoid((lam - keys) * inv_tau))

    def bisect(_, lo_hi):
        lo, hi = lo_hi
        mid = 0.5 * (lo + hi)
        below = f(mid) < demand
        return jnp.where(below, mid, lo), jnp.where(below, hi, mid)

    lo, hi = jax.lax.fori_loop(
        0, n_bisect, bisect, (lam0 - span, lam0 + span))
    return jax.lax.stop_gradient(0.5 * (lo + hi))


def soft_water_level_fixed(keys: jax.Array, widths: jax.Array, demand,
                           lam_hat, inv_tau) -> jax.Array:
    """The differentiable half of `soft_water_level`: one Newton
    correction from an already-solved (stop-gradded) ``lam_hat``. All
    gradient flow of the water level lives here — given the same
    ``lam_hat`` the composition is bitwise-identical to the original
    fused form, which is what lets the custom VJP replay this half from
    a saved residual. Shapes as in `_bisect_level`."""
    lam_hat = jax.lax.stop_gradient(lam_hat)
    span = _WL_SIGMA_SPAN / inv_tau
    z = (lam_hat - keys) * inv_tau
    sig = jax.nn.sigmoid(z)
    # f' uses sigmoid(-z), not 1 - sig: once the marginal segment's sig
    # rounds to 1 (the level sits ~17 tau above its key when its width
    # exceeds the demand by one ulp) 1 - sig is 0, f' hits the floor and
    # the step jumps to -span, emptying every segment
    denom = jnp.maximum(
        jax.lax.stop_gradient(_lane_sum(widths * sig * jax.nn.sigmoid(-z))
                              * inv_tau), _WL_TINY)
    step = (demand - _lane_sum(widths * sig)) / denom
    return lam_hat + jnp.clip(step, -span, span)


def soft_dispatch_hour(prev: jax.Array, dwell: jax.Array,
                       avail: jax.Array, keys: jax.Array,
                       order: jax.Array, demand, *, inv_tau, inv_tau_mw,
                       min_dwell: int, n_bisect: int = 30,
                       onehot: bool = False) -> tuple[jax.Array, jax.Array]:
    """One hour of the temperature-``tau`` softmin water-fill — the
    relaxation of `dispatch_alloc_hour`.

    Shared *verbatim* by `soft_dispatch_ref` and the Pallas kernel
    (`repro.kernels.soft_dispatch`), exactly like `dispatch_alloc_hour`,
    so the two soft paths are bit-identical. Same segment model (locked
    / retain / fresh), but every hard choice is smoothed:

      * the greedy fill becomes the entropic water-fill
        ``x_j = w_j sigmoid((lam - key_j) / tau)`` with ``lam`` from
        `soft_water_level` — a softmin over the (price − migrate
        premium) keys that spreads marginal mass across nearby segments
        and converges to the exact clip-fill as tau -> 0;
      * the dwell lock becomes a smooth discount: lock strength
        ``sigmoid((dwell - 1/2) / tau_cnt)`` of the held mass (the hard
        ``dwell > 0`` gate on the integer-valued limit), the countdown
        ``relu(dwell - 1)`` becomes its softplus at the same count
        temperature (the hard chain parks the counter exactly on both
        kinks — smoothing them is what makes the gradients
        finite-difference-checkable), and the fresh-placement reset
        becomes a sigmoid of the allocation *increase* at MW
        temperature ``tau_mw = 1 / inv_tau_mw``.

    ``keys`` are the host-precomputed [3S] segment keys of
    `repro.dispatch.segment_keys` and ``order`` their ascending sort —
    reused to seed the water-level bracket with the hard level (count
    the sorted widths' cumulative mass past the demand). The fill is
    renormalised to sum exactly to the demand (scale -> 1 as tau -> 0),
    which also zeroes allocation on zero-demand padded hours.
    prev/dwell/avail: [S] or [1, S]; keys/order: [3S] or [1, 3S].
    ``onehot`` picks the gather form as in `dispatch_alloc_hour`.
    Returns ``(alloc, dwell')`` shaped like ``prev``.
    """
    alloc, dwell, _ = soft_dispatch_hour_parts(
        prev, dwell, avail, keys, order, demand, inv_tau=inv_tau,
        inv_tau_mw=inv_tau_mw, min_dwell=min_dwell, n_bisect=n_bisect,
        onehot=onehot)
    return alloc, dwell


def _hour_widths(prev: jax.Array, dwell: jax.Array, avail: jax.Array, *,
                 inv_tau, min_dwell: int) -> jax.Array:
    """[..., 3S] locked / retain / fresh segment widths of one hour."""
    held = jnp.minimum(prev, avail)
    if min_dwell > 0:
        inv_tau_cnt = inv_tau / _DWELL_CNT_SCALE
        locked = jax.nn.sigmoid((dwell - 0.5) * inv_tau_cnt) * held
    else:
        locked = jnp.zeros_like(held)
    return jnp.concatenate([locked, held - locked, avail - held], axis=-1)


def soft_dispatch_hour_fixed(prev: jax.Array, dwell: jax.Array,
                             avail: jax.Array, keys: jax.Array, demand,
                             lam_hat, inv_tau, inv_tau_mw, *,
                             min_dwell: int
                             ) -> tuple[jax.Array, jax.Array]:
    """One soft-dispatch hour given an already-bisected water level.

    The differentiable core of `soft_dispatch_hour`: every op that
    carries gradient (widths, Newton correction, fill, renormalisation,
    dwell dynamics) — only the stop-gradded solver state (``lam_hat``
    from `_bisect_level`, which also subsumes the sorted hard-level
    seed) is taken as an input. Needs no ``order``, no sort walk and no
    bisection, which is exactly what the fused custom VJP exploits: the
    forward saves ``lam_hat`` per hour, and the backward is the
    `jax.vjp` transpose of *this* function — the same linear map native
    autodiff would build, just replayed from slim residuals
    (`soft_dispatch_hour_grad`). Runs on rows like
    `dispatch_alloc_hour`; outputs are shaped like ``prev``.
    """
    shape = jnp.shape(prev)
    prev, dwell, avail, keys, demand, lam_hat = map(
        _row, (prev, dwell, avail, keys, demand, lam_hat))
    s = prev.shape[-1]
    widths = _hour_widths(prev, dwell, avail, inv_tau=inv_tau,
                          min_dwell=min_dwell)
    lam = soft_water_level_fixed(keys, widths, demand, lam_hat, inv_tau)

    fill = widths * jax.nn.sigmoid((lam - keys) * inv_tau)
    fill = fill * (demand / jnp.maximum(_lane_sum(fill),
                                        1e-9 * demand + _WL_TINY))
    alloc = fill[:, :s] + fill[:, s:2 * s] + fill[:, 2 * s:]
    if min_dwell > 0:
        inv_tau_cnt = inv_tau / _DWELL_CNT_SCALE
        moved_in = jax.nn.sigmoid((alloc - prev - DWELL_EVENT_MW)
                                  * inv_tau_mw)
        count_down = jax.nn.softplus((dwell - 1.0) * inv_tau_cnt) \
            / inv_tau_cnt
        dwell = moved_in * min_dwell + (1.0 - moved_in) * count_down
    return alloc.reshape(shape), dwell.reshape(shape)


def soft_dispatch_hour_parts(prev: jax.Array, dwell: jax.Array,
                             avail: jax.Array, keys: jax.Array,
                             order: jax.Array, demand, *, inv_tau,
                             inv_tau_mw, min_dwell: int,
                             n_bisect: int = 30, onehot: bool = False
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`soft_dispatch_hour` that also returns the bisected level
    ``lam_hat`` — the one extra per-hour residual the fused VJP saves.
    ``(alloc, dwell')`` are bitwise those of `soft_dispatch_hour`; the
    sort walk uses the exact gathers (``onehot`` as there) and the
    doubling prefix sum of `dispatch_alloc_hour`."""
    shape, lam_shape = jnp.shape(prev), jnp.shape(demand)
    prev, dwell, avail, keys, order, demand = map(
        _row, (prev, dwell, avail, keys, order, demand))
    s = prev.shape[-1]
    widths = _hour_widths(prev, dwell, avail, inv_tau=inv_tau,
                          min_dwell=min_dwell)
    cums = doubling_prefix(_gather_lanes(widths, order, onehot))
    marginal = jnp.minimum(_lane_sum((cums < demand).astype(jnp.int32)),
                           3 * s - 1)
    sorted_keys = _gather_lanes(keys, order, onehot)
    pos = jax.lax.broadcasted_iota(jnp.int32, sorted_keys.shape, 1)
    lam0 = jax.lax.stop_gradient(
        _lane_sum(jnp.where(pos == marginal, sorted_keys, 0.0)))
    lam_hat = _bisect_level(keys, widths, demand, lam0, inv_tau,
                            n_bisect=n_bisect)
    alloc, dwell = soft_dispatch_hour_fixed(
        prev, dwell, avail, keys, demand, lam_hat, inv_tau, inv_tau_mw,
        min_dwell=min_dwell)
    return (alloc.reshape(shape), dwell.reshape(shape),
            lam_hat.reshape(lam_shape))


def soft_dispatch_hour_grad(prev: jax.Array, dwell: jax.Array,
                            avail: jax.Array, keys: jax.Array, demand,
                            lam_hat, inv_tau, inv_tau_mw,
                            u_alloc: jax.Array, u_dwell: jax.Array, *,
                            min_dwell: int):
    """Adjoint of one fixed-level hour: the exact `jax.vjp` transpose of
    `soft_dispatch_hour_fixed` under output cotangents ``(u_alloc,
    u_dwell)``. Shared verbatim by the XLA and Pallas fused backwards
    and by the sequential `soft_dispatch_grad_ref` oracle — the same
    role `soft_gate_grad` plays for the isolated scan. Returns
    ``(d_prev, d_dwell, d_avail, d_keys, d_demand, d_inv_tau,
    d_inv_tau_mw)``; linear in the cotangents, so zero-padded hours
    contribute exact zeros and padding needs no masking.
    """
    def fwd(p, dw, av, ke, de, it, itm):
        return soft_dispatch_hour_fixed(p, dw, av, ke, de, lam_hat,
                                        it, itm, min_dwell=min_dwell)

    _, pull = jax.vjp(fwd, prev, dwell, avail, keys, demand,
                      inv_tau, inv_tau_mw)
    return pull((u_alloc, u_dwell))


def soft_dispatch_grad_ref(avail: jax.Array, keys: jax.Array,
                           order: jax.Array, demand: jax.Array,
                           g: jax.Array, *, tau, min_dwell: int = 0,
                           mw_scale: float = 0.05, n_bisect: int = 30):
    """Sequential oracle for the fused soft-dispatch backward.

    Pulls the output cotangent ``g`` ([S, T], against the allocation of
    `soft_dispatch_ref`) back through the hour recurrence: a forward
    scan records each hour's entering state and bisected level, a
    reverse scan chains `soft_dispatch_hour_grad` carrying the adjoints
    of the (prev alloc, dwell) state. Returns ``(d_avail [S, T],
    d_keys [T, 3S], d_demand [T], d_tau [])`` — the same quantities
    native autodiff produces, to float round-off, and the contract the
    blocked XLA/Pallas backwards in `repro.kernels.soft_dispatch` are
    tested against (exactly as `soft_scan_grad_ref` anchors the
    isolated scan's VJP).
    """
    a = jnp.asarray(avail)
    dtype = a.dtype if jnp.issubdtype(a.dtype, jnp.floating) \
        else jnp.float32
    a = a.astype(dtype)
    s = a.shape[0]
    keys = jnp.asarray(keys, dtype)
    demand = jnp.asarray(demand, dtype)
    g = jnp.asarray(g, dtype)
    tau = jnp.asarray(tau, dtype)
    inv_tau = 1.0 / tau
    inv_tau_mw = inv_tau / jnp.asarray(mw_scale, dtype)

    def fstep(carry, inp):
        prev, dwell = carry
        a_t, k_t, o_t, d_t = inp
        alloc, dwell2, lam_hat = soft_dispatch_hour_parts(
            prev, dwell, a_t, k_t, o_t, d_t, inv_tau=inv_tau,
            inv_tau_mw=inv_tau_mw, min_dwell=min_dwell,
            n_bisect=n_bisect)
        return (alloc, dwell2), (prev, dwell, lam_hat)

    zeros = jnp.zeros((s,), dtype)
    _, (prevs, dwells_in, lam_hats) = jax.lax.scan(
        fstep, (zeros, zeros),
        (a.T, keys, jnp.asarray(order, jnp.int32), demand))

    def bstep(carry, inp):
        u_prev, u_dwell, acc_it, acc_itm = carry
        p_t, dw_t, lam_t, a_t, k_t, d_t, g_t = inp
        d_p, d_dw, d_av, d_ke, d_de, d_it, d_itm = \
            soft_dispatch_hour_grad(p_t, dw_t, a_t, k_t, d_t, lam_t,
                                    inv_tau, inv_tau_mw, g_t + u_prev,
                                    u_dwell, min_dwell=min_dwell)
        return (d_p, d_dw, acc_it + d_it, acc_itm + d_itm), \
            (d_av, d_ke, d_de)

    init = (zeros, zeros, jnp.zeros((), dtype), jnp.zeros((), dtype))
    (_, _, acc_it, acc_itm), (d_av, d_ke, d_de) = jax.lax.scan(
        bstep, init, (prevs, dwells_in, lam_hats, a.T, keys, demand, g.T),
        reverse=True)
    # tau -> (inv_tau, inv_tau_mw) chain: d itau/d tau = -itau^2,
    # d itaumw/d tau = -itau * itaumw
    d_tau = -(inv_tau ** 2) * acc_it - inv_tau * inv_tau_mw * acc_itm
    return d_av.T, d_ke, d_de, d_tau


def soft_dispatch_ref(avail: jax.Array, keys: jax.Array, order: jax.Array,
                      demand: jax.Array, *, tau, min_dwell: int = 0,
                      mw_scale: float = 0.05,
                      n_bisect: int = 30) -> jax.Array:
    """Sequential oracle for the soft (differentiable) dispatch scan.

    avail: [S, T] available MW; keys/order: [T, 3S] precomputed segment
    keys (`repro.dispatch.segment_keys`) and their ascending sort
    permutation; demand: [T] MW. Returns the relaxed allocation [S, T],
    differentiable in ``avail``, ``demand``, ``keys`` and ``tau``, and
    converging to `dispatch_ref`'s hard allocation as tau -> 0 (at
    problems whose segment keys are distinct). ``mw_scale`` sets the MW
    temperature of the dwell reset gate as ``tau * mw_scale`` — it
    co-anneals with ``tau``. Computation runs in the availability dtype
    (float64 under x64 — the FD gradient checks rely on this), exactly
    like `soft_scan_ref`.
    """
    a = jnp.asarray(avail)
    dtype = a.dtype if jnp.issubdtype(a.dtype, jnp.floating) else jnp.float32
    a = a.astype(dtype)
    s = a.shape[0]
    keys = jnp.asarray(keys, dtype)
    demand = jnp.asarray(demand, dtype)
    inv_tau = 1.0 / jnp.asarray(tau, dtype)
    inv_tau_mw = inv_tau / jnp.asarray(mw_scale, dtype)

    def step(carry, inp):
        prev, dwell = carry
        a_t, k_t, o_t, d_t = inp
        alloc, dwell = soft_dispatch_hour(
            prev, dwell, a_t, k_t, o_t, d_t, inv_tau=inv_tau,
            inv_tau_mw=inv_tau_mw, min_dwell=min_dwell,
            n_bisect=n_bisect)
        return (alloc, dwell), alloc

    zeros = jnp.zeros((s,), dtype)
    _, alloc_t = jax.lax.scan(
        step, (zeros, zeros),
        (a.T, keys, jnp.asarray(order, jnp.int32), demand))
    return alloc_t.T


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  q_offset: int = 0) -> jax.Array:
    """q: [B,Sq,H,Dh]; k,v: [B,Skv,G,Dh]; GQA by head repetition."""
    b, sq, h, dh = q.shape
    skv, g = k.shape[1], k.shape[2]
    rep = h // g
    kh = jnp.repeat(k, rep, axis=2)
    vh = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kh.astype(jnp.float32)) * dh ** -0.5
    qpos = q_offset + jnp.arange(sq)
    kpos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         valid: jax.Array) -> jax.Array:
    """q: [B,1,H,Dh]; k,v: [B,W,G,Dh]; valid: [B,W] bool."""
    b, _, h, dh = q.shape
    g = k.shape[2]
    rep = h // g
    kh = jnp.repeat(k, rep, axis=2)
    vh = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kh.astype(jnp.float32)) * dh ** -0.5
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, a: jax.Array,
            b: jax.Array, c: jax.Array,
            h0: Optional[jax.Array] = None
            ) -> tuple[jax.Array, jax.Array]:
    """Sequential (unchunked) SSD recurrence — the ground-truth oracle.

    x: [B,S,H,P]; dt: [B,S,H] (post-softplus); a: [H] (negative);
    b,c: [B,S,G,N]; h0: [B,H,P,N] or None.
    Returns y: [B,S,H,P] (f32), h_last: [B,H,P,N] (f32).

        h_t = h_{t-1} * exp(dt_t a) + dt_t x_t b_t^T ;  y_t = h_t c_t
    """
    bsz, s, nh, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = nh // g
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)   # [B,S,H,N]
    ch = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp          # [B,H,P], [B,H], [B,H,N], [B,H,N]
        decay = jnp.exp(dt_t * a[None, :])                 # [B,H]
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt_t, x_t, b_t)
        h = h * decay[..., None, None] + upd
        y_t = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y_t

    h_init = jnp.zeros((bsz, nh, p, n), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    h_last, ys = jax.lax.scan(
        step, h_init,
        (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
         bh.swapaxes(0, 1), ch.swapaxes(0, 1)))
    return ys.swapaxes(0, 1), h_last
