"""Fused, checkpointed custom VJP for the soft-state trajectory.

`repro.kernels.soft_scan.soft_state` evaluates the relaxed hysteresis
recurrence s_t = alpha_t s_{t-1} + beta_t with one
`jax.lax.associative_scan`, and PR 2's tuner differentiated it with
native autodiff. That works, but the autodiff rule for an associative
scan transposes every combine of the O(log T)-depth tree: the backward
pass re-materialises the full [B, T] affine-map intermediates (several
buffers of them) in HBM on every Adam step, and its arithmetic is 3-4x
the forward's. This module replaces it with a hand-written
`jax.custom_vjp` built on rematerialisation over time blocks:

  forward   evaluate s blockwise (within-block prefix scan + an exact
            [n_blocks]-length carry propagation) and save as residuals
            only the inputs plus the per-block *entering* states —
            O(B * T / block_t) extra memory instead of O(B * T).

  backward  walk the time grid in reverse, one block at a time:
            recompute the gates and the within-block states from the
            saved carry (checkpointed recompute, block-local), run the
            adjoint recurrence u_t = g_t + alpha_{t+1} u_{t+1} — itself
            a first-order linear recurrence, evaluated with the same
            blocked machinery in reverse — and apply the per-hour chain
            rule `repro.kernels.ref.soft_gate_grad`, which is shared
            verbatim with the sequential oracle
            `repro.kernels.ref.soft_scan_grad_ref`.

Two implementations sit behind the same custom_vjp, mirroring
`fleet_scan`: a blocked pure-XLA form (the fast path off-TPU —
sequential in time, vectorized over rows, and dtype-following so the
float64 parity tests are exact), and a Pallas TPU kernel pair
(time-innermost grid, carries in VMEM scratch, log-depth doubling
scans in-block, the backward visiting time blocks in reverse via its
index map; validated in interpret mode, like the other kernels in this
package). Gradients
agree with native autodiff through `soft_state` to tight tolerance —
the reassociation of the time reduction is the only difference — and
cotangents are produced for all four primals (prices, p_on, p_off,
tau), so the annealed tuner's traced tau needs no special casing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import soft_gate_grad, soft_gates

DEFAULT_BLOCK_T = 256


# ---------------------------------------------------------------------------
# blocked XLA path (fast path off-TPU; dtype-following)
# ---------------------------------------------------------------------------
#
# XLA:CPU runs a tight `lax.scan` over T (one [B]-wide fused vector op
# per hour) several times faster than the log-depth associative scan the
# native path uses — the scan's strided odd/even slicing is hostile to
# caches, and its autodiff rule is worse still. So off-TPU the fused
# path is sequential in time and vectorized over rows, exactly like the
# `ref.py` oracles, with the backward walking block by block so its
# transients stay O(B * block_t) instead of O(B * T).

def _xla_fwd(p, p_on, p_off, inv_tau, block_t):
    """Forward state trajectory + the per-block entering states.

    Time-major sequential scan; the checkpoint carries are a gather of
    states already computed (s at block boundaries), so saving them
    costs nothing beyond the O(B * T / block_t) residual itself.
    """
    b, t = p.shape
    _, _, alpha, beta = soft_gates(p.T, p_on[None, :], p_off[None, :],
                                   inv_tau)                   # [T, B]

    def step(s, ab):
        a_t, b_t = ab
        s = a_t * s + b_t
        return s, s

    _, s_tm = jax.lax.scan(step, jnp.ones((b,), p.dtype), (alpha, beta))
    s = s_tm.T                                                # [B, T]
    nb = -(-t // block_t)
    ones = jnp.ones((b, 1), p.dtype)
    if nb == 1:
        return s, ones
    idx = jnp.arange(1, nb) * block_t - 1    # state entering blocks 1..
    return s, jnp.concatenate([ones, s[:, idx]], axis=1)


def _xla_bwd(p, p_on, p_off, inv_tau, carries, g, block_t):
    """Checkpointed backward: walk the time grid in reverse, one block
    at a time — recompute gates and states block-locally from the saved
    entering carry, run the adjoint recurrence u_t = g_t + alpha_{t+1}
    u_{t+1} across the block (seeded over the boundary by the later
    block's first hour), then apply the shared per-hour chain rule
    `soft_gate_grad` and accumulate the parameter sums. Transients are
    O(B * block_t) per block plus the d_prices output (dead code the
    compiler can drop when prices carry no cotangent — the tuner's
    case)."""
    b, t = p.shape
    pad = (-t) % block_t
    nb = (t + pad) // block_t
    p_blk = jnp.pad(p.T, ((0, pad), (0, 0))).reshape(nb, block_t, b)
    g_blk = jnp.pad(g.T, ((0, pad), (0, 0))).reshape(nb, block_t, b)
    valid = (jnp.arange(nb * block_t) < t).astype(p.dtype) \
        .reshape(nb, block_t, 1)

    def block_step(carry, xs):
        u_next, a_next, acc = carry          # adjoint seed from block j+1
        p_b, g_b, c_in, v_b = xs             # [bt, B], [bt, B], [B], [bt, 1]
        a, f, alpha, beta = soft_gates(p_b, p_on[None, :], p_off[None, :],
                                       inv_tau)
        alpha = alpha * v_b + (1.0 - v_b)    # identity maps past T
        g_b = g_b * v_b

        def fstep(s, ab):
            a_t, b_t = ab
            return a_t * s + b_t, s          # emit the *entering* state

        _, s_prev = jax.lax.scan(fstep, c_in, (alpha, beta * v_b))

        def bstep(c, ab):
            u_n, a_n = c
            g_t, a_t = ab
            u_t = g_t + a_n * u_n
            return (u_t, a_t), u_t

        (u_first, a_first), u = jax.lax.scan(
            bstep, (u_next, a_next), (g_b, alpha), reverse=True)

        d_p, d_on, d_off, d_it = soft_gate_grad(
            p_b, s_prev, u, p_on[None, :], p_off[None, :], inv_tau,
            gates=(a, f))
        acc = (acc[0] + jnp.sum(d_on * v_b, axis=0),
               acc[1] + jnp.sum(d_off * v_b, axis=0),
               acc[2] + jnp.sum(d_it * v_b, axis=0))
        return (u_first, a_first, acc), d_p * v_b

    zeros = jnp.zeros((b,), p.dtype)
    (_, _, acc), d_p_blk = jax.lax.scan(
        block_step, (zeros, zeros, (zeros, zeros, zeros)),
        (p_blk, g_blk, carries.T, valid), reverse=True)
    d_p = d_p_blk.reshape(nb * block_t, b)[:t].T
    return d_p, acc[0], acc[1], jnp.sum(acc[2])


# ---------------------------------------------------------------------------
# Pallas TPU kernels (time-innermost grid, carries in VMEM scratch)
# ---------------------------------------------------------------------------

def _prefix_linear(coeff: jax.Array, acc: jax.Array) -> jax.Array:
    """In-kernel prefix of s_i = coeff_i s_{i-1} + acc_i (s_{-1} folded
    into acc_0) along axis 0 by log-depth doubling: shifted-in zeros
    terminate both the value and the running product past the edge."""
    n = coeff.shape[0]
    s, prod = acc, coeff
    d = 1
    while d < n:
        zeros = jnp.zeros((d,) + s.shape[1:], s.dtype)
        s = s + prod * jnp.concatenate([zeros, s[:-d]], axis=0)
        prod = prod * jnp.concatenate([zeros, prod[:-d]], axis=0)
        d *= 2
    return s


def _suffix_linear(coeff: jax.Array, acc: jax.Array) -> jax.Array:
    """Mirror of `_prefix_linear` for u_i = acc_i + coeff_i u_{i+1}
    (the seed from beyond the block folded into acc_{-1})."""
    n = coeff.shape[0]
    u, prod = acc, coeff
    d = 1
    while d < n:
        zeros = jnp.zeros((d,) + u.shape[1:], u.dtype)
        u = u + prod * jnp.concatenate([u[d:], zeros], axis=0)
        prod = prod * jnp.concatenate([prod[d:], zeros], axis=0)
        d *= 2
    return u


def _fwd_kernel(p_ref, pon_ref, poff_ref, itau_ref,
                s_ref, carr_ref,
                state_scr, *, t_total: int, block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        state_scr[...] = jnp.ones_like(state_scr)      # s_{-1} = 1

    p = p_ref[...].astype(jnp.float32)                 # [bt, bb] time-major
    pon = pon_ref[...]
    poff = poff_ref[...]
    inv_tau = itau_ref[0]
    tloc = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    valid = (ti * block_t + tloc) < t_total

    _, _, alpha, beta = soft_gates(p, pon, poff, inv_tau)
    alpha = jnp.where(valid, alpha, 1.0)               # identity padding
    beta = jnp.where(valid, beta, 0.0)

    carry = state_scr[...]                             # [1, bb]
    carr_ref[...] = carry                              # entering state
    # fold the entering state into acc_0 (static-slice concat, not a
    # scatter — lowers cleanly on the VPU)
    beta = jnp.concatenate([beta[:1] + alpha[:1] * carry,
                            beta[1:]], axis=0)
    s = _prefix_linear(alpha, beta)
    s_ref[...] = s
    state_scr[...] = s[block_t - 1:]


def _bwd_kernel(p_ref, g_ref, pon_ref, poff_ref, itau_ref, carr_ref,
                dp_ref, sums_ref,
                u_scr, afirst_scr, acc_scr,
                *, t_total: int, block_t: int, n_t_blocks: int):
    ti = pl.program_id(1)                # visits time blocks in reverse
                                         # via the index maps

    @pl.when(ti == 0)
    def _init():
        u_scr[...] = jnp.zeros_like(u_scr)        # no hours after T-1
        afirst_scr[...] = jnp.zeros_like(afirst_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    pon = pon_ref[...]
    poff = poff_ref[...]
    inv_tau = itau_ref[0]
    bi = n_t_blocks - 1 - ti                       # actual time-block index
    tloc = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    valid = (bi * block_t + tloc) < t_total

    a_gate, f_gate, alpha, beta = soft_gates(p, pon, poff, inv_tau)
    alpha = jnp.where(valid, alpha, 1.0)
    beta = jnp.where(valid, beta, 0.0)
    g = jnp.where(valid, g, 0.0)

    # recompute the block's states from the saved entering carry
    carry = carr_ref[...]                          # [1, bb]
    beta_f = jnp.concatenate([beta[:1] + alpha[:1] * carry,
                              beta[1:]], axis=0)
    s = _prefix_linear(alpha, beta_f)
    s_prev = jnp.concatenate([carry, s[:-1]], axis=0)

    # adjoint within the block, seeded across the boundary by the later
    # block's first-hour adjoint: u_t = g_t + alpha_{t+1} u_{t+1}
    coeff = jnp.concatenate([alpha[1:],
                             jnp.zeros((1,) + alpha.shape[1:],
                                       alpha.dtype)], axis=0)
    seed = afirst_scr[...] * u_scr[...]            # [1, bb]
    g = jnp.concatenate([g[:-1], g[-1:] + seed], axis=0)
    u = _suffix_linear(coeff, g)
    u_scr[...] = u[:1]
    afirst_scr[...] = alpha[:1]

    d_p, d_on, d_off, d_it = soft_gate_grad(p, s_prev, u, pon, poff,
                                            inv_tau, gates=(a_gate, f_gate))
    vf = valid.astype(jnp.float32)
    dp_ref[...] = d_p * vf
    acc_scr[...] += jnp.concatenate(
        [jnp.sum(v * vf, axis=0, keepdims=True) for v in (d_on, d_off, d_it)],
        axis=0)

    @pl.when(ti == n_t_blocks - 1)
    def _finish():
        sums_ref[...] = acc_scr[...]


def _pick_block(n: int, cap: int) -> int:
    """Largest 128-multiple <= min(cap, n), or n itself for small n."""
    cap = max(min(cap, n), 1)
    return (cap // 128) * 128 if cap >= 128 else cap


def _pallas_pad(p, p_on, p_off, block_b, block_t):
    b, t = p.shape
    pad_b = (-b) % block_b
    pad_t = (-t) % block_t
    p_tm = jnp.pad(p.astype(jnp.float32).T, ((0, pad_t), (0, pad_b)))
    # params ride as [1, B*] rows: Mosaic tiles a 1-D operand differently
    # from XLA
    pon = jnp.pad(p_on.astype(jnp.float32), (0, pad_b))[None, :]
    poff = jnp.pad(p_off.astype(jnp.float32), (0, pad_b))[None, :]
    return p_tm, pon, poff


@functools.partial(jax.jit, static_argnames=("block_b", "block_t",
                                             "t_total", "interpret"))
def _pallas_fwd(p_tm, pon, poff, itau, *, block_b, block_t, t_total,
                interpret):
    t_pad, b_pad = p_tm.shape
    nb, nt = b_pad // block_b, t_pad // block_t
    kernel = functools.partial(_fwd_kernel, t_total=t_total,
                               block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_t, block_b), lambda bi, ti: (ti, bi)),
            pl.BlockSpec((1, block_b), lambda bi, ti: (0, bi)),
            pl.BlockSpec((1, block_b), lambda bi, ti: (0, bi)),
            pl.BlockSpec((1,), lambda bi, ti: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, block_b), lambda bi, ti: (ti, bi)),
            pl.BlockSpec((None, 1, block_b), lambda bi, ti: (ti, 0, bi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, b_pad), jnp.float32),
            jax.ShapeDtypeStruct((nt, 1, b_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.float32)],
        interpret=interpret,
    )(p_tm, pon, poff, itau)


@functools.partial(jax.jit, static_argnames=("block_b", "block_t",
                                             "t_total", "interpret"))
def _pallas_bwd(p_tm, g_tm, pon, poff, itau, carr, *, block_b, block_t,
                t_total, interpret):
    t_pad, b_pad = p_tm.shape
    nb, nt = b_pad // block_b, t_pad // block_t
    kernel = functools.partial(_bwd_kernel, t_total=t_total,
                               block_t=block_t, n_t_blocks=nt)
    rev = lambda bi, ti: (nt - 1 - ti, bi)         # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((1, block_b), lambda bi, ti: (0, bi)),
            pl.BlockSpec((1, block_b), lambda bi, ti: (0, bi)),
            pl.BlockSpec((1,), lambda bi, ti: (0,)),
            pl.BlockSpec((None, 1, block_b),
                         lambda bi, ti: (nt - 1 - ti, 0, bi)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, block_b), rev),
            pl.BlockSpec((3, block_b), lambda bi, ti: (0, bi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, b_pad), jnp.float32),
            jax.ShapeDtypeStruct((3, b_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.float32),
                        pltpu.VMEM((1, block_b), jnp.float32),
                        pltpu.VMEM((3, block_b), jnp.float32)],
        interpret=interpret,
    )(p_tm, g_tm, pon, poff, itau, carr)


# ---------------------------------------------------------------------------
# the custom_vjp
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _soft_state(p, p_on, p_off, tau, block_t, use_pallas, interpret):
    s, _ = _soft_state_fwd(p, p_on, p_off, tau, block_t, use_pallas,
                           interpret)
    return s


def _soft_state_fwd(p, p_on, p_off, tau, block_t, use_pallas, interpret):
    inv_tau = 1.0 / tau
    if use_pallas:
        b, t = p.shape
        block_b = _pick_block(b, 128)
        bt = _pick_block(t, block_t)
        p_tm, pon, poff = _pallas_pad(p, p_on, p_off, block_b, bt)
        itau = jnp.asarray(inv_tau, jnp.float32).reshape(1)
        s_tm, carr = _pallas_fwd(p_tm, pon, poff, itau, block_b=block_b,
                                 block_t=bt, t_total=t,
                                 interpret=interpret)
        s = s_tm[:t, :b].T.astype(p.dtype)
        carries = carr[:, 0, :b].T.astype(p.dtype)
    else:
        s, carries = _xla_fwd(p, p_on, p_off, inv_tau, block_t)
    # residuals: inputs + per-block entering states — O(B * T / block_t)
    # beyond buffers that already exist, never the [B, T] intermediates
    return s, (p, p_on, p_off, tau, carries)


def _soft_state_bwd(block_t, use_pallas, interpret, res, g):
    p, p_on, p_off, tau, carries = res
    inv_tau = 1.0 / tau
    if use_pallas:
        b, t = p.shape
        block_b = _pick_block(b, 128)
        bt = _pick_block(t, block_t)
        p_tm, pon, poff = _pallas_pad(p, p_on, p_off, block_b, bt)
        g_tm = jnp.pad(g.astype(jnp.float32).T,
                       ((0, (-t) % bt), (0, (-b) % block_b)))
        itau = jnp.asarray(inv_tau, jnp.float32).reshape(1)
        carr = jnp.pad(carries.astype(jnp.float32).T,
                       ((0, 0), (0, (-b) % block_b)))[:, None, :]
        dp_tm, sums = _pallas_bwd(p_tm, g_tm, pon, poff, itau, carr,
                                  block_b=block_b, block_t=bt, t_total=t,
                                  interpret=interpret)
        d_p = dp_tm[:t, :b].T.astype(p.dtype)
        d_on = sums[0, :b].astype(p.dtype)
        d_off = sums[1, :b].astype(p.dtype)
        d_it = jnp.sum(sums[2, :b]).astype(p.dtype)
    else:
        d_p, d_on, d_off, d_it = _xla_bwd(p, p_on, p_off, inv_tau,
                                          carries, g, block_t)
    d_tau = (-inv_tau ** 2 * d_it).astype(jnp.result_type(tau))
    return d_p, d_on, d_off, d_tau


_soft_state.defvjp(_soft_state_fwd, _soft_state_bwd)


def _auto_pallas(use_pallas: Optional[bool]) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


def soft_state_fused(prices: jax.Array, p_on: jax.Array, p_off: jax.Array,
                     *, tau, block_t: int = DEFAULT_BLOCK_T,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Drop-in replacement for `soft_scan.soft_state` with a fused,
    checkpointed VJP.

    Same contract (prices [B, T]; p_on/p_off [B] broadcastable; initial
    state 1) and the same forward values up to summation order; the
    backward saves only per-block carries and rematerialises gates
    block-locally, so an Adam step's residual footprint drops from
    O(B*T) affine intermediates to O(B*T/block_t). ``use_pallas=None``
    auto-selects the TPU kernel pair on TPU and the blocked XLA form
    elsewhere (the Pallas interpreter is a debugging tool, not a fast
    path). Differentiable in all of (prices, p_on, p_off, tau).
    """
    p = jnp.asarray(prices)
    dtype = p.dtype if jnp.issubdtype(p.dtype, jnp.floating) else jnp.float32
    p = p.astype(dtype)
    b = p.shape[0]
    p_on = jnp.broadcast_to(jnp.asarray(p_on, dtype), (b,))
    p_off = jnp.broadcast_to(jnp.asarray(p_off, dtype), (b,))
    tau = jnp.asarray(tau, dtype)
    use_pallas = _auto_pallas(use_pallas)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _soft_state(p, p_on, p_off, tau, int(block_t), use_pallas,
                       bool(interpret))
