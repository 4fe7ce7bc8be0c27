"""Batched stateful threshold/hysteresis scan as a Pallas TPU kernel.

The fleet backtesting engine needs, for B = N x M x K scenario rows and a
[B, T] price block, four per-row sums (see `repro.kernels.ref.FleetScanOut`)
driven by a per-row two-threshold state machine. A naive formulation is a
sequential scan over T — hostile to the VPU. The kernel instead removes the
time recurrence *inside* each block with a last-decisive-event trick:

    on_t  = 0 if p_t > p_off, 1 if p_t <= p_on, else on_{t-1}

is "state of the most recent decisive sample". Encoding each decisive
sample as ev_t = 2 t + on_t (on/off are mutually exclusive since
p_on <= p_off) and taking a running max over time yields, per element, the
index *and* decision of the latest event in one prefix max — no serial
loop; samples before the first event inherit the carry from the previous
block. The prefix max is log-depth shift-and-`maximum` over sublanes
(`kernels.ref.doubling_prefix`): Mosaic has no `cummax` lowering, and
max is exact, so any association gives the same bits.

Layout: time-major [T, B] blocks (rows ride the 128-lane axis, the running
max runs along sublanes). Grid = (n_row_blocks, n_time_blocks) with time
innermost, so the on/off carry and the four accumulators live in VMEM
scratch across time blocks — zero HBM round-trips for state, exactly the
pattern of `ssd_scan.py`. Padding in T is masked in-kernel against the true
length; padding in B is sliced off by the wrapper.

Validated in interpret mode against `repro.kernels.ref.fleet_scan_ref`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import FleetScanOut, doubling_prefix


def _fleet_kernel(p_ref, pon_ref, poff_ref, lvl_ref, idle_ref,   # inputs
                  out_ref,                                       # [4, bb]
                  state_scr, acc_scr,                            # scratch
                  *, block_t: int, n_t_blocks: int, t_total: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        state_scr[...] = jnp.ones_like(state_scr)   # start running
        acc_scr[...] = jnp.zeros_like(acc_scr)

    p = p_ref[...].astype(jnp.float32)              # [bt, bb] time-major
    pon = pon_ref[...]                              # [1, bb]
    poff = poff_ref[...]
    lvl = lvl_ref[...]
    idle = idle_ref[...]

    tloc = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    valid = (ti * block_t + tloc) < t_total         # [bt, bb] T-padding mask

    on_ev = (p <= pon) & valid
    off_ev = (p > poff) & valid
    # ev = 2t for an off event, 2t+1 for an on event, -1 otherwise; the
    # running max is then the latest decisive event and its low bit the
    # state it imposed.
    ev = jnp.where(on_ev | off_ev,
                   2 * tloc + on_ev.astype(jnp.int32), -1)
    last = doubling_prefix(ev, jnp.maximum, -1, axis=0)  # [bt, bb]

    carry = state_scr[...]                          # [1, bb] in {0, 1}
    on = jnp.where(last >= 0, (last & 1).astype(jnp.float32),
                   carry)                           # [bt, bb]
    on_prev = jnp.concatenate([carry, on[:-1]], axis=0)
    starts = jnp.maximum(on - on_prev, 0.0)         # only at valid samples

    vf = valid.astype(jnp.float32)
    cap = lvl + (1.0 - lvl) * on
    draw = cap + idle * (1.0 - cap)
    acc_scr[...] += jnp.concatenate(
        [jnp.sum(v, axis=0, keepdims=True)
         for v in (draw * p * vf, cap * vf, starts, starts * p)], axis=0)
    # events on invalid samples are masked, so the last row is the state
    # at the last valid sample even in a partially (or fully) padded block.
    state_scr[...] = on[block_t - 1:]

    @pl.when(ti == n_t_blocks - 1)
    def _finish():
        out_ref[...] = acc_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_t", "t_total",
                                    "interpret"))
def _fleet_scan_padded(p_tm: jax.Array, pon: jax.Array, poff: jax.Array,
                       lvl: jax.Array, idle: jax.Array, *,
                       block_b: int, block_t: int, t_total: int,
                       interpret: bool) -> jax.Array:
    """Core pallas_call over padded, time-major inputs.

    p_tm: [T*, B*] (block multiples); params: [1, B*] (2-D: Mosaic tiles
    a 1-D operand differently from XLA). Returns [4, B*].
    """
    t_pad, b_pad = p_tm.shape
    nb, nt = b_pad // block_b, t_pad // block_t

    kernel = functools.partial(_fleet_kernel, block_t=block_t,
                               n_t_blocks=nt, t_total=t_total)
    return pl.pallas_call(
        kernel,
        grid=(nb, nt),
        in_specs=[
            pl.BlockSpec((block_t, block_b), lambda bi, ti: (ti, bi)),
        ] + [pl.BlockSpec((1, block_b), lambda bi, ti: (0, bi))] * 4,
        out_specs=pl.BlockSpec((4, block_b), lambda bi, ti: (0, bi)),
        out_shape=jax.ShapeDtypeStruct((4, b_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_b), jnp.float32),
                        pltpu.VMEM((4, block_b), jnp.float32)],
        interpret=interpret,
    )(p_tm, pon, poff, lvl, idle)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pick_block(n: int, cap: int) -> int:
    """Largest 128-multiple <= min(cap, n), or n itself for small n."""
    cap = max(min(cap, n), 1)
    return (cap // 128) * 128 if cap >= 128 else cap


def fleet_scan(prices: jax.Array, p_on: jax.Array, p_off: jax.Array,
               off_level: jax.Array, idle_frac: jax.Array, *,
               block_b: int = 128, block_t: int = 512,
               interpret: Optional[bool] = None) -> FleetScanOut:
    """Batched hysteresis scan. prices: [B, T]; params: [B] (broadcastable).

    Same contract as `repro.kernels.ref.fleet_scan_ref`, which requires
    ``p_on <= p_off`` (the event encoding gives "on" precedence inside an
    inverted band, the reference gives "off" — `repro.fleet.grid`
    validates this). This is the hot inner loop of
    `repro.fleet.engine.backtest`.
    """
    p = jnp.asarray(prices, jnp.float32)
    b, t = p.shape
    block_b = _pick_block(b, block_b)
    block_t = _pick_block(t, block_t)
    pad_b = (-b) % block_b
    pad_t = (-t) % block_t

    p_tm = jnp.pad(p.T, ((0, pad_t), (0, pad_b)))    # [T*, B*] time-major
    def _param(v):
        return jnp.pad(jnp.broadcast_to(jnp.asarray(v, jnp.float32), (b,)),
                       (0, pad_b))[None, :]
    out = _fleet_scan_padded(
        p_tm, _param(p_on), _param(p_off), _param(off_level),
        _param(idle_frac), block_b=block_b, block_t=block_t, t_total=t,
        interpret=_auto_interpret(interpret))
    return FleetScanOut(out[0, :b], out[1, :b], out[2, :b], out[3, :b])
