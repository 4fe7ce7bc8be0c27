"""Public jit'd entry points for the Pallas kernels.

These wrappers own everything the kernels should not: layout transposes
into the kernel-native [B, heads, seq, feature] form, padding to block
multiples (padded KV is masked via ``kv_len`` / validity, padded Q rows are
sliced off), block-size selection (hardware-aligned 128-multiples when the
shape allows), and interpret-mode auto-detection (interpret=True off-TPU so
the same code path is testable on CPU).

The model swaps these in for its XLA blockwise implementations when
``cfg.attn_impl == "pallas"``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_bgrd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssd_scan import ssd_scan_grouped


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block(n: int, cap: int) -> int:
    """Hardware-friendly block: the largest 128-multiple <= min(cap, n)
    (or n itself when n < 128 — small smoke shapes)."""
    cap = max(min(cap, n), 1)
    if cap >= 128:
        return (cap // 128) * 128
    return cap


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0, bq: int = 512, bkv: int = 512,
                    interpret: Optional[bool] = None) -> jax.Array:
    """FlashAttention forward. q: [B,Sq,H,Dh]; k,v: [B,Skv,G,Dh];
    returns [B,Sq,H,Dh]. Causal/window masks are positional with
    ``q_offset`` added to query positions (chunked prefill)."""
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    bq = _block(sq, bq)
    bkv = _block(skv, bkv)

    qt = _pad_to(q.transpose(0, 2, 1, 3), 2, bq)       # [B,H,Sq*,Dh]
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bkv)      # [B,G,Skv*,Dh]
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bkv)

    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               q_offset=q_offset, bq=bq, bkv=bkv,
                               kv_len=skv,
                               interpret=_auto_interpret(interpret))
    return out[:, :, :sq].transpose(0, 2, 1, 3)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, bkv: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """One-token attention against a cache. q: [B,1,H,Dh];
    k,v: [B,W,G,Dh]; valid: [B,W] bool. Returns [B,1,H,Dh]."""
    b, _, h, dh = q.shape
    w, g = k.shape[1], k.shape[2]
    r = h // g
    bkv = _block(w, bkv)

    qg = q.reshape(b, g, r, dh)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 2, bkv)      # [B,G,W*,Dh]
    vt = _pad_to(v.transpose(0, 2, 1, 3), 2, bkv)
    vm = _pad_to(valid.astype(jnp.int8), 1, bkv)[:, None]   # [B,1,W*]

    out = decode_attention_bgrd(qg, kt, vt, vm, bkv=bkv,
                                interpret=_auto_interpret(interpret))
    return out.reshape(b, 1, h, dh)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
             b: jax.Array, c: jax.Array, chunk: int,
             h0: Optional[jax.Array] = None, *,
             interpret: Optional[bool] = None
             ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan (Mamba2). Same contract as
    `repro.models.ssm.ssd_chunked`:

    x: [B,S,H,P]; dt: [B,S,H] post-softplus; a: [H] negative;
    b,c: [B,S,G,N]; h0: [B,H,P,N] or None.
    Returns y: [B,S,H,P] (f32), h_last: [B,H,P,N] (f32).
    """
    bsz, s, nh, p = x.shape
    g, n = b.shape[2], b.shape[3]
    pad = (-s) % chunk
    if pad:
        # zero padding is exact: dt=0 -> decay 1, zero state contribution
        zp = lambda t: jnp.pad(t, [(0, 0), (0, pad)]   # noqa: E731
                               + [(0, 0)] * (t.ndim - 2))
        y, h_t = ssd_scan(zp(x), zp(dt), a, zp(b), zp(c), chunk, h0,
                          interpret=interpret)
        return y[:, :s], h_t
    nc = s // chunk

    xk = x.transpose(0, 2, 1, 3).reshape(bsz, nh, nc, chunk, p)
    dtk = dt.transpose(0, 2, 1).reshape(bsz, nh, nc, chunk, 1)
    bk = b.transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    ck = c.transpose(0, 2, 1, 3).reshape(bsz, g, nc, chunk, n)
    h0k = jnp.zeros((bsz, nh, n, p), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32).transpose(0, 1, 3, 2)

    y, h_t = ssd_scan_grouped(xk, dtk, a.astype(jnp.float32), bk, ck, h0k,
                              l_chunk=chunk, n_groups=g,
                              interpret=_auto_interpret(interpret))
    y = y.reshape(bsz, nh, s, p).transpose(0, 2, 1, 3)
    return y, h_t.transpose(0, 1, 3, 2)
