"""Single-token (decode) attention over a KV cache as a Pallas TPU kernel.

Decode attention is memory-bound: one query token per sequence reads the
whole [W, G, Dh] cache. The kernel streams KV blocks through VMEM with an
online-softmax carry (same sequential-grid pattern as flash_attention), but
the q block is the *GQA group* — all R = H/G query heads that share one KV
head are processed together, turning R separate [1, Dh] @ [Dh, bkv] GEMVs
into one [R, bkv] matmul. With R = 5..8 on the assigned GQA configs this is
the difference between wasting 127/128 MXU rows and wasting (128-R)/128 —
and it amortises each KV byte over R heads, which matters more: the roofline
for decode is HBM bandwidth, and bytes/step ~ cache size / R per head.

Validity is slot-based (``pos_buf`` semantics from the model's AttnCache):
a mask row [W] accompanies the cache, so rolling (sliding-window) caches and
linear caches use the same kernel.

Validated in interpret mode against `repro.kernels.ref.decode_attention_ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_scr, l_scr, acc_scr,
                   *, scale: float, bkv: int, n_kv: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                # [R, dh]
    k = k_ref[0, 0].astype(jnp.float32)                # [bkv, dh]
    v = v_ref[0, 0]                                    # [bkv, dh]
    valid = valid_ref[...] != 0                        # [1, bkv] int8 -> bool

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)                   # [R, bkv]

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
    m_scr[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def decode_attention_bgrd(q: jax.Array, k: jax.Array, v: jax.Array,
                          valid: jax.Array, *, bkv: int,
                          interpret: bool) -> jax.Array:
    """Core pallas_call. q: [B,G,R,Dh]; k,v: [B,G,W,Dh] (W a multiple of
    ``bkv``); valid: [B,1,W] int8 (the unit axis keeps the mask block's
    last two dims tile-legal). Returns [B,G,R,Dh]."""
    b, g, r, dh = q.shape
    w = k.shape[2]
    n_kv = w // bkv

    kernel = functools.partial(_decode_kernel, scale=dh ** -0.5,
                               bkv=bkv, n_kv=n_kv)

    return pl.pallas_call(
        kernel,
        grid=(b, g, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, r, dh), lambda b_, g_, j: (b_, g_, 0, 0)),
            pl.BlockSpec((1, 1, bkv, dh), lambda b_, g_, j: (b_, g_, j, 0)),
            pl.BlockSpec((1, 1, bkv, dh), lambda b_, g_, j: (b_, g_, j, 0)),
            pl.BlockSpec((None, 1, bkv), lambda b_, g_, j: (b_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, r, dh),
                               lambda b_, g_, j: (b_, g_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, r, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((r,), jnp.float32),
            pltpu.VMEM((r,), jnp.float32),
            pltpu.VMEM((r, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid)
