"""Hour-by-hour feasible fleet dispatch as a Pallas TPU kernel.

The cross-site dispatcher (`repro.dispatch`) allocates a fleet-wide
compute demand across S sites every hour, greedily filling price-sorted
capacity segments (locked / retain-with-migration-premium / fresh — see
`repro.kernels.ref.dispatch_alloc_hour`). The allocation is a true
recurrence over time — the previous hour's placement prices retention
and the dwell counters gate migration — so unlike `fleet_scan` there is
no cummax trick that removes the serial dependence. What *can* be
removed is everything expensive inside an hour:

  * the price sort: segment sort keys depend only on prices and the
    (static) migration premium, never on the running state, so the
    ascending sort permutation of all 3S segments and its inverse are
    precomputed on the host ([T, 3S] int32 each) and streamed through
    the grid like any other input;
  * the per-hour greedy fill: with the permutation in hand, "capacity
    mass at strictly cheaper segments" is gather -> exclusive prefix sum
    -> gather-back, and the fill is a clip. In the kernel the gathers are
    exact one-hot matmuls on the MXU and the prefix sum a log-depth
    doubling, the forms Mosaic lowers (it has no 1-D gather and no
    cumsum); the XLA oracle takes plain gathers, which give the same
    bits at O(S) instead of O(S^2) per hour.

Layout: grid = (n_time_blocks,) with time innermost and [block_t, S]
time-major blocks; the carry (previous allocation + dwell counters, both
[1, S] rows) lives in VMEM scratch across time blocks — zero HBM
round-trips for state, the `fleet_scan.py` / `ssd_scan.py` pattern.
Each hour's demand is a [1, 1] read from a [T, 1] column. Hours inside a block
run under `fori_loop`. Per-hour math is imported from
`repro.kernels.ref.dispatch_alloc_hour`, shared verbatim with the
sequential `dispatch_ref` oracle, so kernel and reference are
bit-identical (asserted in `tests/test_dispatch.py`).

T-padding needs no masking: padded hours carry zero demand and zero
availability, so they allocate nothing, and they sit after every real
hour so their dwell decrements touch no real decision.

VMEM grows with S^2: each hour's one-hot gathers build [3S, 3S]
operands. At a v5e's default 16 MiB of scoped VMEM the kernel compiles
up to S = 512 sites and is refused at S = 1024 (20.5 MiB asked);
`tests/test_tpu_compile.py` compiles S = 64 and S = 256.

Validated in interpret mode against `repro.kernels.ref.dispatch_ref`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import dispatch_alloc_hour


def _dispatch_kernel(a_ref, order_ref, rank_ref, d_ref,   # time-major
                     out_ref,                             # [block_t, S]
                     prev_scr, dwell_scr,                 # [1, S] VMEM carry
                     *, block_t: int, min_dwell: int):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        prev_scr[...] = jnp.zeros_like(prev_scr)    # start empty
        dwell_scr[...] = jnp.zeros_like(dwell_scr)

    def hour(h, carry):
        row = pl.ds(h, 1)                            # one hour, [1, n]
        alloc, dwell = dispatch_alloc_hour(
            prev_scr[...], dwell_scr[...], a_ref[row, :], order_ref[row, :],
            rank_ref[row, :], d_ref[row, :], min_dwell=min_dwell,
            onehot=True)
        out_ref[row, :] = alloc
        prev_scr[...] = alloc
        dwell_scr[...] = dwell
        return carry

    jax.lax.fori_loop(0, block_t, hour, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "min_dwell", "interpret"))
def _dispatch_scan_padded(a_tm: jax.Array, order: jax.Array,
                          rank: jax.Array, demand: jax.Array, *,
                          block_t: int, min_dwell: int,
                          interpret: bool) -> jax.Array:
    """Core pallas_call over padded, time-major inputs.

    a_tm: [T*, S]; order/rank: [T*, 3S]; demand: [T*, 1] (T* a block_t
    multiple; a column, so each hour reads a [1, 1] vector). Returns the
    allocation [T*, S].
    """
    t_pad, s = a_tm.shape
    nt = t_pad // block_t

    kernel = functools.partial(_dispatch_kernel, block_t=block_t,
                               min_dwell=min_dwell)
    return pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 3 * s), lambda ti: (ti, 0)),
            pl.BlockSpec((block_t, 1), lambda ti: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, s), lambda ti: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t_pad, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, s), jnp.float32),
                        pltpu.VMEM((1, s), jnp.float32)],
        interpret=interpret,
    )(a_tm, order, rank, demand)


def time_block(t: int, block_t: int) -> int:
    """Hours per grid step: the whole horizon when it fits in
    ``block_t``, else ``block_t`` rounded down to a multiple of 8 (the
    sublane tile of the [block_t, S] blocks)."""
    return t if t <= block_t else max(block_t // 8 * 8, 8)


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def dispatch_scan(avail: jax.Array, order: jax.Array, rank: jax.Array,
                  demand: jax.Array, *, min_dwell: int = 0,
                  block_t: int = 512,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Feasible dispatch allocation. avail: [S, T] MW; order/rank:
    [T, 3S] precomputed segment sort data
    (`repro.dispatch.segment_rank`); demand: [T] MW. Returns the
    allocation [S, T].

    Same contract as `repro.kernels.ref.dispatch_ref`; this is the hot
    inner loop of `repro.dispatch.dispatch`.
    """
    a = jnp.asarray(avail, jnp.float32)
    s, t = a.shape
    block_t = time_block(t, block_t)
    pad_t = (-t) % block_t

    a_tm = jnp.pad(a.T, ((0, pad_t), (0, 0)))        # [T*, S] time-major
    order_p = jnp.pad(jnp.asarray(order, jnp.int32), ((0, pad_t), (0, 0)))
    rank_p = jnp.pad(jnp.asarray(rank, jnp.int32), ((0, pad_t), (0, 0)))
    d_p = jnp.pad(jnp.asarray(demand, jnp.float32), (0, pad_t))[:, None]
    out = _dispatch_scan_padded(a_tm, order_p, rank_p, d_p,
                                block_t=block_t, min_dwell=int(min_dwell),
                                interpret=_auto_interpret(interpret))
    return out[:t].T
