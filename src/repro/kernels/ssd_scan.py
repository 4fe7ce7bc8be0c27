"""Mamba2 SSD (state-space duality) chunk scan as a Pallas TPU kernel.

The SSD algorithm splits the sequence into chunks of length L. Within a
chunk the recurrence collapses to a masked quadratic ("attention") form —
three MXU matmuls — and between chunks only an [N, P] state is carried.

TPU adaptation: the original Triton kernels split intra/inter-chunk work
into separate launches with the state scan on the host side. On TPU the
grid is *sequential*, so the inter-chunk recurrence becomes a VMEM scratch
carry along the innermost grid dimension — one kernel does the whole scan
with zero HBM round-trips for the state. Grid = (B, H, n_chunks):

    state_scr [N, P] f32   carried across the chunk dimension
    per step:  lmat   = exp(segsum(dt*a))        [L, L]   (VPU)
               scores = (C B^T) * lmat           [L, L]   (MXU)
               y      = scores (x*dt)            [L, P]   (MXU)
               y     += (C state) * exp(cum)     [L, P]   (MXU)
               state  = state*exp(cum[-1]) + B^T (x*dt*decay)   (MXU)

VMEM per step at L=256, P=64, N=128 (f32): x/y 64 KiB, B/C 2x128 KiB,
scores/lmat 2x256 KiB, state 32 KiB -> < 1 MiB, comfortably inside VMEM;
L is the kernel's block knob (cfg.ssm_chunk).

B/C are shared across heads within a group (Mamba2-1.3b: one group), so the
BlockSpec index map (h -> h // heads_per_group) fetches the group block —
no per-head materialisation in HBM.

Validated in interpret mode against `repro.kernels.ref.ssd_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import doubling_prefix


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,   # inputs
                y_ref, hT_ref,                                # outputs
                state_scr,                                    # [N, P] f32
                *, l_chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)     # [N, P]

    x = x_ref[0, 0, 0].astype(jnp.float32)                    # [L, P]
    dt = dt_ref[0, 0, 0].astype(jnp.float32)                  # [L, 1]
    a = a_ref[pl.program_id(1)]                               # scalar (SMEM)
    bmat = b_ref[0, 0, 0].astype(jnp.float32)                 # [L, N]
    cmat = c_ref[0, 0, 0].astype(jnp.float32)                 # [L, N]

    da = dt * a                                               # [L, 1] (<= 0)
    cum = doubling_prefix(da, axis=0)                         # [L, 1]
    cum_last = cum[l_chunk - 1:]                              # [1, 1]
    # the same value lane-broadcast first ([1, 1] -> [N, P] in one step is
    # a broadcast Mosaic does not lower)
    cum_last_p = jnp.broadcast_to(cum, x.shape)[l_chunk - 1:]  # [1, P]
    # segment-sum decay matrix: lmat[i,j] = exp(cum_i - cum_j) for j <= i;
    # the row copy of cum comes from a square transpose, which Mosaic
    # lowers (a [L, 1] -> [1, L] relayout it does not)
    cum_sq = jnp.broadcast_to(cum, (l_chunk, l_chunk))
    diff = cum_sq - cum_sq.T
    tril = (jax.lax.broadcasted_iota(jnp.int32, (l_chunk, l_chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (l_chunk, l_chunk), 1))
    lmat = jnp.where(tril, jnp.exp(diff), 0.0)                # [L, L]

    xdt = x * dt                                              # [L, P]
    scores = jax.lax.dot_general(
        cmat, bmat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * lmat            # [L, L]
    y = jax.lax.dot(scores, xdt,
                    preferred_element_type=jnp.float32)       # [L, P]

    # contribution of the carried-in state
    state = state_scr[...]                                    # [N, P]
    y_off = jax.lax.dot(cmat, state,
                        preferred_element_type=jnp.float32)   # [L, P]
    y += y_off * jnp.exp(cum)

    # state update: h <- h * exp(cum[-1]) + B^T (xdt * decay)
    decay = jnp.exp(cum_last - cum)                           # [L, 1]
    contrib = jax.lax.dot_general(
        bmat, xdt * decay, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [N, P]
    state_scr[...] = state * jnp.exp(cum_last_p) + contrib

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _finish():
        hT_ref[0, 0] = state_scr[...].astype(hT_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("l_chunk", "n_groups", "interpret"))
def ssd_scan_grouped(x: jax.Array, dt: jax.Array, a: jax.Array,
                     b: jax.Array, c: jax.Array, h0: jax.Array, *,
                     l_chunk: int, n_groups: int,
                     interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Core pallas_call.

    x:  [B, H, NC, L, P]      (conv-activated inputs, head-split)
    dt: [B, H, NC, L, 1]      (post-softplus step sizes, one column per
                               chunk: time runs along sublanes like x)
    a:  [H]                   (negative decay coefficients; SMEM)
    b/c:[B, G, NC, L, N]      (G groups; heads share group blocks)
    h0: [B, H, N, P]          (initial state, zeros for training)
    Returns y: [B, H, NC, L, P] and final state [B, H, N, P] (f32).
    """
    bsz, nh, nc, l, p = x.shape
    n = b.shape[-1]
    rep = nh // n_groups

    kernel = functools.partial(_ssd_kernel, l_chunk=l, n_chunks=nc)

    return pl.pallas_call(
        kernel,
        grid=(bsz, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, l, 1),
                         lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, l, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, l, n),
                         lambda b_, h_, c_: (b_, h_ // rep, c_, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, l, p),
                         lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nh, nc, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c, h0)
