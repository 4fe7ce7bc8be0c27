"""Differentiable (temperature-relaxed) cross-site dispatch.

`repro.kernels.dispatch_scan` answers "where does the load run under
these schedules?" but its greedy water-fill allocates through argsort
comparisons and clips, so per-site policy parameters can shape the
dispatch only through zero-measure kinks — a site that never receives
load gets no gradient at all. This module relaxes the per-hour greedy
fill with the *entropic* water-fill at temperature ``tau``:

    x_j = w_j sigmoid((lam - key_j) / tau),   sum_j x_j = demand

the unique optimum of  min_x sum key_j x_j + tau * H(x; w)  over the
capacity box — a softmin over the (price − migrate-premium) segment
keys. As tau -> 0 the sigmoids harden into the exact greedy clip-fill
(`repro.kernels.ref.dispatch_alloc_hour`), and for tau > 0 every
segment carries allocation mass proportional to how close its key sits
to the water level, so gradients see *all* sites — the signal that lets
`repro.tune` teach each site its fleet role (the swing-site effect).
Dwell locks are discounted smoothly (lock strength ``min(dwell, 1)``,
sigmoid fresh-placement reset at a co-annealed MW temperature), so the
hour-to-hour recurrence stays differentiable end to end.

The water level ``lam`` has no closed form; it is found by fixed-count
bisection seeded from the *hard* water level — which the
host-precomputed `repro.dispatch.segment_rank` sort yields in O(S) —
under ``stop_gradient``, with one differentiable Newton step providing
the exact first-order implicit gradient (`repro.kernels.ref.
soft_water_level`). Per-hour math is `repro.kernels.ref.
soft_dispatch_hour`, shared *verbatim* with the sequential
`soft_dispatch_ref` oracle, so kernel and reference are bit-identical.

Layout mirrors `dispatch_scan`: off-TPU the public entry point runs the
jitted sequential-in-time `lax.scan` form (dtype-following, so float64
FD gradient checks are exact); on TPU a Pallas kernel with grid =
(n_time_blocks,), time innermost, [block_t, S] time-major blocks and
the (prev alloc, dwell) carry in VMEM scratch — zero HBM round-trips
for state. T-padding needs no masking: padded hours carry zero demand,
and the renormalised fill is exactly zero there. Validated in
interpret mode against `soft_dispatch_ref`. The per-hour one-hot gathers
hold [3S, 3S] operands in VMEM: at a v5e's default 16 MiB of scoped VMEM
the kernels compile up to S = 256 sites and are refused at S = 512
(about 41 MiB asked).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch_scan import time_block
from repro.kernels.ref import (soft_dispatch_hour, soft_dispatch_hour_grad,
                               soft_dispatch_hour_parts, soft_dispatch_ref)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)   # whole (1,) scalar operand


def _soft_dispatch_kernel(a_ref, keys_ref, order_ref, d_ref,  # time-major
                          itau_ref, itaumw_ref,               # (1,) SMEM
                          out_ref,                            # [block_t, S]
                          prev_scr, dwell_scr,                # [1, S] carry
                          *, block_t: int, min_dwell: int, n_bisect: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        prev_scr[...] = jnp.zeros_like(prev_scr)     # start empty
        dwell_scr[...] = jnp.zeros_like(dwell_scr)

    inv_tau = itau_ref[0]
    inv_tau_mw = itaumw_ref[0]

    def hour(h, carry):
        row = pl.ds(h, 1)                             # one hour, [1, n]
        alloc, dwell = soft_dispatch_hour(
            prev_scr[...], dwell_scr[...], a_ref[row, :], keys_ref[row, :],
            order_ref[row, :], d_ref[row, :], inv_tau=inv_tau,
            inv_tau_mw=inv_tau_mw, min_dwell=min_dwell,
            n_bisect=n_bisect, onehot=True)
        out_ref[row, :] = alloc
        prev_scr[...] = alloc
        dwell_scr[...] = dwell
        return carry

    jax.lax.fori_loop(0, block_t, hour, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "min_dwell",
                                             "n_bisect", "interpret"))
def _soft_dispatch_padded(a_tm: jax.Array, keys: jax.Array,
                          order: jax.Array, demand: jax.Array,
                          itau: jax.Array, itaumw: jax.Array, *,
                          block_t: int, min_dwell: int, n_bisect: int,
                          interpret: bool) -> jax.Array:
    """Core pallas_call over padded, time-major inputs.

    a_tm: [T*, S]; keys/order: [T*, 3S]; demand: [T*, 1] (a column:
    each hour reads a [1, 1] vector); itau/itaumw: (1,) in SMEM (T* a
    block_t multiple). Returns the allocation [T*, S].
    """
    t_pad, s = a_tm.shape
    nt = t_pad // block_t

    kernel = functools.partial(_soft_dispatch_kernel, block_t=block_t,
                               min_dwell=min_dwell, n_bisect=n_bisect)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti: (ti, 0)),
            _SMEM,
            _SMEM,
        ],
        out_specs=pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t_pad, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((1, s), jnp.float32)],
        interpret=interpret,
    )(a_tm, keys, order, demand, itau, itaumw)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def soft_dispatch_pallas(avail: jax.Array, keys: jax.Array,
                         order: jax.Array, demand: jax.Array, *,
                         tau, min_dwell: int = 0, mw_scale: float = 0.05,
                         n_bisect: int = 30, block_t: int = 512,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Pallas form of the soft dispatch scan (f32; forward only — the
    differentiable path is the XLA scan in `soft_dispatch`). Same
    contract as `repro.kernels.ref.soft_dispatch_ref`; bit-identical to
    it (asserted in `tests/test_soft_dispatch.py`)."""
    a = jnp.asarray(avail, jnp.float32)
    s, t = a.shape
    block_t = time_block(t, block_t)
    pad_t = (-t) % block_t

    a_tm = jnp.pad(a.T, ((0, pad_t), (0, 0)))        # [T*, S] time-major
    keys_p = jnp.pad(jnp.asarray(keys, jnp.float32), ((0, pad_t), (0, 0)))
    order_p = jnp.pad(jnp.asarray(order, jnp.int32), ((0, pad_t), (0, 0)))
    d_p = jnp.pad(jnp.asarray(demand, jnp.float32), (0, pad_t))[:, None]
    itau = (1.0 / jnp.asarray(tau, jnp.float32)).reshape(1)
    itaumw = itau / jnp.float32(mw_scale)
    out = _soft_dispatch_padded(a_tm, keys_p, order_p, d_p, itau, itaumw,
                                block_t=block_t, min_dwell=int(min_dwell),
                                n_bisect=int(n_bisect),
                                interpret=_auto_interpret(interpret))
    return out[:t].T


# ---------------------------------------------------------------------------
# Fused custom VJP: slim residuals (alloc, entering dwell, bisected level)
# instead of native autodiff's per-hour intermediate stash, and a backward
# that never re-runs the bisection or the sort walk — the per-hour adjoint
# is `repro.kernels.ref.soft_dispatch_hour_grad`, the exact `jax.vjp`
# transpose of the shared fixed-level hour, so fused gradients match
# native autodiff to float round-off (and `soft_dispatch_grad_ref`
# anchors both). Structure mirrors `repro.kernels.soft_scan_vjp`: an XLA
# scan pair off-TPU, a Pallas kernel pair (time-innermost grid, state
# adjoints in VMEM scratch, reversed block index maps in the backward) on
# TPU, selected by the same `use_pallas` / `interpret` knobs.
# ---------------------------------------------------------------------------


def _xla_fused_fwd(a, keys, order, demand, inv_tau, inv_tau_mw, *,
                   min_dwell: int, n_bisect: int):
    """Forward scan that also emits the VJP residuals.

    Returns ``(alloc [S, T], dwell_in [S, T], lam_hat [T])`` where
    ``dwell_in`` is each hour's *entering* dwell state (the prev-alloc
    entering state needs no residual — it is the output shifted by one
    hour) and ``lam_hat`` the stop-gradded bisection solution the
    backward replays the Newton correction from.
    """
    s = a.shape[0]

    def step(carry, inp):
        prev, dwell = carry
        a_t, k_t, o_t, d_t = inp
        alloc, dwell2, lam_hat = soft_dispatch_hour_parts(
            prev, dwell, a_t, k_t, o_t, d_t, inv_tau=inv_tau,
            inv_tau_mw=inv_tau_mw, min_dwell=min_dwell,
            n_bisect=n_bisect)
        return (alloc, dwell2), (alloc, dwell, lam_hat)

    zeros = jnp.zeros((s,), a.dtype)
    _, (alloc_t, dwin_t, lam_t) = jax.lax.scan(
        step, (zeros, zeros), (a.T, keys, order, demand))
    return alloc_t.T, dwin_t.T, lam_t


def _xla_fused_bwd(a, keys, demand, inv_tau, inv_tau_mw, alloc, dwin,
                   lam, g, *, min_dwell: int):
    """Reverse scan carrying the (prev alloc, dwell) state adjoints.

    Linear in ``g``, so the zero cotangents of padded hours contribute
    exact zeros — same no-masking contract as the forward. Returns
    ``(d_avail, d_keys, d_demand, sum d_inv_tau, sum d_inv_tau_mw)``.
    """
    s = a.shape[0]
    prev = jnp.concatenate([jnp.zeros_like(alloc[:, :1]),
                            alloc[:, :-1]], axis=1)

    def step(carry, inp):
        u_prev, u_dwell, acc_it, acc_itm = carry
        p_t, dw_t, lam_t, a_t, k_t, d_t, g_t = inp
        d_p, d_dw, d_av, d_ke, d_de, d_it, d_itm = \
            soft_dispatch_hour_grad(p_t, dw_t, a_t, k_t, d_t, lam_t,
                                    inv_tau, inv_tau_mw, g_t + u_prev,
                                    u_dwell, min_dwell=min_dwell)
        return (d_p, d_dw, acc_it + d_it, acc_itm + d_itm), \
            (d_av, d_ke, d_de)

    zeros = jnp.zeros((s,), a.dtype)
    zero = jnp.zeros((), a.dtype)
    (_, _, acc_it, acc_itm), (d_av, d_ke, d_de) = jax.lax.scan(
        step, (zeros, zeros, zero, zero),
        (prev.T, dwin.T, lam, a.T, keys, demand, g.T), reverse=True)
    return d_av.T, d_ke, d_de, acc_it, acc_itm


def _fused_fwd_kernel(a_ref, keys_ref, order_ref, d_ref,      # time-major
                      itau_ref, itaumw_ref,                   # (1,) SMEM
                      out_ref, dwin_ref, lam_ref,             # residuals out
                      prev_scr, dwell_scr,                    # [1, S] carry
                      *, block_t: int, min_dwell: int, n_bisect: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        prev_scr[...] = jnp.zeros_like(prev_scr)
        dwell_scr[...] = jnp.zeros_like(dwell_scr)

    inv_tau = itau_ref[0]
    inv_tau_mw = itaumw_ref[0]

    def hour(h, carry):
        row = pl.ds(h, 1)
        dwin_ref[row, :] = dwell_scr[...]            # entering dwell
        alloc, dwell, lam_hat = soft_dispatch_hour_parts(
            prev_scr[...], dwell_scr[...], a_ref[row, :], keys_ref[row, :],
            order_ref[row, :], d_ref[row, :], inv_tau=inv_tau,
            inv_tau_mw=inv_tau_mw, min_dwell=min_dwell,
            n_bisect=n_bisect, onehot=True)
        out_ref[row, :] = alloc
        lam_ref[row, :] = lam_hat
        prev_scr[...] = alloc
        dwell_scr[...] = dwell
        return carry

    jax.lax.fori_loop(0, block_t, hour, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "min_dwell",
                                             "n_bisect", "interpret"))
def _pallas_fused_fwd(a_tm, keys, order, demand, itau, itaumw, *,
                      block_t: int, min_dwell: int, n_bisect: int,
                      interpret: bool):
    """pallas_call of the residual-emitting forward over padded,
    time-major inputs (same layout as `_soft_dispatch_padded`; the level
    residual is a [T*, 1] column like the demand)."""
    t_pad, s = a_tm.shape
    nt = t_pad // block_t

    kernel = functools.partial(_fused_fwd_kernel, block_t=block_t,
                               min_dwell=min_dwell, n_bisect=n_bisect)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti: (ti, 0)),
            _SMEM,
            _SMEM,
        ],
        out_specs=[pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
                   pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
                   pl.BlockSpec((block_t, 1), lambda ti: (ti, 0))],
        out_shape=[jax.ShapeDtypeStruct((t_pad, s), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, s), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((1, s), jnp.float32)],
        interpret=interpret,
    )(a_tm, keys, order, demand, itau, itaumw)


def _fused_bwd_kernel(prev_ref, dwin_ref, lam_ref, a_ref, keys_ref,
                      d_ref, g_ref, itau_ref, itaumw_ref,
                      dav_ref, dke_ref, dde_ref, sums_ref,
                      uprev_scr, udwell_scr, acc_scr,
                      *, block_t: int, min_dwell: int, n_t_blocks: int):
    """One reversed time block of the backward: the index maps walk
    blocks last-to-first, hours run block_t-1 .. 0 inside, and the
    (prev, dwell) adjoints cross block boundaries in VMEM scratch. The
    two tau-chain accumulators ride along in a [2, 1] scratch and are
    emitted once, from the final (earliest-time) block."""
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        uprev_scr[...] = jnp.zeros_like(uprev_scr)
        udwell_scr[...] = jnp.zeros_like(udwell_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    inv_tau = itau_ref[0]
    inv_tau_mw = itaumw_ref[0]

    def hour(i, carry):
        row = pl.ds(block_t - 1 - i, 1)
        d_p, d_dw, d_av, d_ke, d_de, d_it, d_itm = \
            soft_dispatch_hour_grad(
                prev_ref[row, :], dwin_ref[row, :], a_ref[row, :],
                keys_ref[row, :], d_ref[row, :], lam_ref[row, :], inv_tau,
                inv_tau_mw, g_ref[row, :] + uprev_scr[...],
                udwell_scr[...], min_dwell=min_dwell)
        dav_ref[row, :] = d_av
        dke_ref[row, :] = d_ke
        dde_ref[row, :] = d_de
        uprev_scr[...] = d_p
        udwell_scr[...] = d_dw
        acc_scr[0:1, :] += jnp.reshape(d_it, (1, 1))
        acc_scr[1:2, :] += jnp.reshape(d_itm, (1, 1))
        return carry

    jax.lax.fori_loop(0, block_t, hour, 0)

    @pl.when(ti == n_t_blocks - 1)
    def _emit():
        sums_ref[...] = acc_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "min_dwell",
                                             "interpret"))
def _pallas_fused_bwd(prev_tm, dwin_tm, lam, a_tm, keys, demand, g_tm,
                      itau, itaumw, *, block_t: int, min_dwell: int,
                      interpret: bool):
    t_pad, s = a_tm.shape
    nt = t_pad // block_t
    rev2 = lambda ti: (nt - 1 - ti, 0)          # noqa: E731

    kernel = functools.partial(_fused_bwd_kernel, block_t=block_t,
                               min_dwell=min_dwell, n_t_blocks=nt)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, s), rev2),
            pl.BlockSpec((block_t, s), rev2),
            pl.BlockSpec((block_t, 1), rev2),
            pl.BlockSpec((block_t, s), rev2),
            pl.BlockSpec((block_t, 3 * s), rev2),
            pl.BlockSpec((block_t, 1), rev2),
            pl.BlockSpec((block_t, s), rev2),
            _SMEM,
            _SMEM,
        ],
        out_specs=[pl.BlockSpec((block_t, s), rev2),
                   pl.BlockSpec((block_t, 3 * s), rev2),
                   pl.BlockSpec((block_t, 1), rev2),
                   pl.BlockSpec((2, 1), lambda ti: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((t_pad, s), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, 3 * s), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((2, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((2, 1), jnp.float32)],
        interpret=interpret,
    )(prev_tm, dwin_tm, lam, a_tm, keys, demand, g_tm, itau, itaumw)


def _pallas_pad(x, pad_t, val=0.0):
    # The backward pads avail/demand with ones, not zeros: an all-zero
    # hour makes the fill renorm divide by the 1e-30 floor, whose
    # square underflows to 0 in f32 and turns the division transpose
    # into 0/0 — NaN even under the padded hours' all-zero cotangents.
    # A well-conditioned dummy hour keeps the padded adjoints exactly
    # zero instead (the VJP is linear in the cotangents).
    return jnp.pad(jnp.asarray(x, jnp.float32),
                   ((0, pad_t),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=val)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _soft_dispatch_fused(avail, keys, order, demand, tau, min_dwell,
                         mw_scale, n_bisect, block_t, use_pallas,
                         interpret):
    alloc, _, _ = _fused_primal(avail, keys, order, demand, tau,
                                min_dwell, mw_scale, n_bisect, block_t,
                                use_pallas, interpret)
    return alloc


def _fused_primal(avail, keys, order, demand, tau, min_dwell, mw_scale,
                  n_bisect, block_t, use_pallas, interpret):
    s, t = avail.shape
    inv_tau = 1.0 / tau
    inv_tau_mw = inv_tau / jnp.asarray(mw_scale, tau.dtype)
    if not use_pallas:
        return _xla_fused_fwd(avail, keys, jnp.asarray(order, jnp.int32),
                              demand, inv_tau, inv_tau_mw,
                              min_dwell=min_dwell, n_bisect=n_bisect)
    bt = time_block(t, block_t)
    pad_t = (-t) % bt
    alloc_tm, dwin_tm, lam = _pallas_fused_fwd(
        _pallas_pad(avail.T, pad_t), _pallas_pad(keys, pad_t),
        jnp.pad(jnp.asarray(order, jnp.int32), ((0, pad_t), (0, 0))),
        _pallas_pad(demand[:, None], pad_t),
        jnp.asarray(inv_tau, jnp.float32).reshape(1),
        jnp.asarray(inv_tau_mw, jnp.float32).reshape(1),
        block_t=bt, min_dwell=min_dwell, n_bisect=n_bisect,
        interpret=_auto_interpret(interpret))
    return alloc_tm[:t].T, dwin_tm[:t].T, lam[:t, 0]


def _fused_fwd(avail, keys, order, demand, tau, min_dwell, mw_scale,
               n_bisect, block_t, use_pallas, interpret):
    alloc, dwin, lam = _fused_primal(avail, keys, order, demand, tau,
                                     min_dwell, mw_scale, n_bisect,
                                     block_t, use_pallas, interpret)
    return alloc, (avail, keys, demand, tau, alloc, dwin, lam,
                   np.shape(order))


def _fused_bwd(min_dwell, mw_scale, n_bisect, block_t, use_pallas,
               interpret, res, g):
    avail, keys, demand, tau, alloc, dwin, lam, order_shape = res
    inv_tau = 1.0 / tau
    inv_tau_mw = inv_tau / jnp.asarray(mw_scale, tau.dtype)
    if not use_pallas:
        d_av, d_ke, d_de, acc_it, acc_itm = _xla_fused_bwd(
            avail, keys, demand, inv_tau, inv_tau_mw, alloc, dwin, lam,
            g, min_dwell=min_dwell)
    else:
        s, t = avail.shape
        bt = time_block(t, block_t)
        pad_t = (-t) % bt
        prev = jnp.concatenate([jnp.zeros_like(alloc[:, :1]),
                                alloc[:, :-1]], axis=1)
        d_av_tm, d_ke, d_de, sums = _pallas_fused_bwd(
            _pallas_pad(prev.T, pad_t), _pallas_pad(dwin.T, pad_t),
            _pallas_pad(lam[:, None], pad_t),
            _pallas_pad(avail.T, pad_t, 1.0), _pallas_pad(keys, pad_t),
            _pallas_pad(demand[:, None], pad_t, 1.0),
            _pallas_pad(g.T, pad_t),
            jnp.asarray(inv_tau, jnp.float32).reshape(1),
            jnp.asarray(inv_tau_mw, jnp.float32).reshape(1),
            block_t=bt, min_dwell=min_dwell,
            interpret=_auto_interpret(interpret))
        d_av = d_av_tm[:t].T.astype(avail.dtype)
        d_ke = d_ke[:t].astype(keys.dtype)
        d_de = d_de[:t, 0].astype(demand.dtype)
        acc_it, acc_itm = sums[0, 0], sums[1, 0]
    # tau -> (inv_tau, inv_tau_mw) chain (see soft_dispatch_grad_ref)
    d_tau = (-(inv_tau ** 2) * acc_it
             - inv_tau * inv_tau_mw * acc_itm).astype(tau.dtype)
    d_order = np.zeros(order_shape, jax.dtypes.float0)
    return d_av, d_ke, d_order, d_de, d_tau


_soft_dispatch_fused.defvjp(_fused_fwd, _fused_bwd)


def soft_dispatch_fused(avail: jax.Array, keys: jax.Array,
                        order: jax.Array, demand: jax.Array, *, tau,
                        min_dwell: int = 0, mw_scale: float = 0.05,
                        n_bisect: int = 30, block_t: int = 512,
                        use_pallas: Optional[bool] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """`soft_dispatch` under the fused custom VJP: same allocation (the
    forward runs the same per-hour math), same gradients to float
    round-off, but the backward replays the Newton correction from
    saved ``lam_hat`` residuals instead of transposing through the
    stashed intermediates of the native scan — no bisection, no sort
    walk, O(S·T) residual memory. Dtype-following off-TPU (the f64 FD
    checks run through here); the Pallas pair is f32.
    """
    a = jnp.asarray(avail)
    dtype = a.dtype if jnp.issubdtype(a.dtype, jnp.floating) \
        else jnp.float32
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        dtype = jnp.float32
    return _soft_dispatch_fused(
        a.astype(dtype), jnp.asarray(keys, dtype),
        jnp.asarray(order, jnp.int32), jnp.asarray(demand, dtype),
        jnp.asarray(tau, dtype), int(min_dwell), float(mw_scale),
        int(n_bisect), int(block_t), bool(use_pallas), interpret)


_soft_dispatch_ref_jit = jax.jit(
    soft_dispatch_ref, static_argnames=("min_dwell", "n_bisect"))


def soft_dispatch(avail: jax.Array, keys: jax.Array, order: jax.Array,
                  demand: jax.Array, *, tau, min_dwell: int = 0,
                  mw_scale: float = 0.05, n_bisect: int = 30,
                  block_t: int = 512,
                  use_pallas: Optional[bool] = None,
                  fused: bool = False) -> jax.Array:
    """Differentiable fleet dispatch allocation at temperature ``tau``.

    avail: [S, T] MW; keys/order: [T, 3S] precomputed segment keys and
    sort (`repro.dispatch.segment_keys` / `segment_rank`); demand: [T]
    MW. Returns the relaxed allocation [S, T], converging to
    `repro.kernels.ref.dispatch_ref` as tau -> 0.

    ``use_pallas=None`` auto-selects like `repro.dispatch.dispatch`:
    the Pallas kernel on TPU, the jitted sequential scan elsewhere.
    Called *inside* a jit (the tuner's soft objective) it traces the
    scan form directly, which is the path gradients flow through.

    ``fused=True`` routes through `soft_dispatch_fused` — the same
    allocation under the custom VJP, whose backward replays from slim
    residuals instead of transposing the native scan (the fast path
    for dispatch-aware tuning).
    """
    if fused:
        return soft_dispatch_fused(avail, keys, order, demand, tau=tau,
                                   min_dwell=min_dwell,
                                   mw_scale=mw_scale, n_bisect=n_bisect,
                                   block_t=block_t,
                                   use_pallas=use_pallas)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return soft_dispatch_pallas(avail, keys, order, demand, tau=tau,
                                    min_dwell=min_dwell,
                                    mw_scale=mw_scale, n_bisect=n_bisect,
                                    block_t=block_t)
    return _soft_dispatch_ref_jit(avail, keys, order, demand, tau=tau,
                                  min_dwell=min_dwell, mw_scale=mw_scale,
                                  n_bisect=n_bisect)


def soft_shed(avail_total: jax.Array, demand: jax.Array, tau, *,
              mw_scale: float = 0.05) -> jax.Array:
    """Smoothed per-hour shed: how much of ``demand`` [T] exceeds the
    fleet's total availability ``avail_total`` [T], relaxed at the same
    MW-space temperature the water-fill uses (``tau * mw_scale`` — the
    scale `soft_dispatch` applies to every MW sigmoid, so shed and
    allocation co-anneal).

        shed_t = w * softplus((demand_t - avail_total_t) / w),
        w = max(tau * mw_scale, 1e-9)

    converging to ``relu(demand - avail_total)`` — the exact shortfall
    the hard dispatcher sheds under `repro.dispatch.Relief` — as
    tau -> 0. Smooth everywhere, so gradients see the VoLL price of an
    *approaching* infeasibility before the hard boundary is crossed."""
    d = jnp.asarray(demand)
    w = jnp.maximum(jnp.asarray(tau, d.dtype) * d.dtype.type(mw_scale),
                    d.dtype.type(1e-9))
    return w * jax.nn.softplus((d - avail_total) / w)
