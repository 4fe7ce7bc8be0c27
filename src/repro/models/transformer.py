"""Model assembly: blocks composed via scan-over-layers for all families.

Families:
  dense   pre-norm GQA attention + SwiGLU MLP (qwen*, stablelm, internvl2
          backbone)
  moe     GQA attention + top-k expert FFN (mixtral, grok-1); optional SWA
  ssm     Mamba2/SSD blocks (mamba2-1.3b)
  hybrid  Mamba2 blocks with a weight-shared attention block applied every
          `hybrid_attn_every` layers (zamba2, simplified: the shared block
          is a standard pre-norm attn+MLP pair; Zamba2's LoRA adapters and
          embedding concat are omitted — noted in DESIGN.md)
  audio   whisper enc-dec: bidirectional encoder over precomputed frame
          embeddings (conv frontend stub), causal decoder w/ cross-attn
  vlm     dense backbone; first `vis_tokens` positions take precomputed
          patch embeddings (InternViT frontend stub)

Layer parameters are stacked on a leading axis and consumed by
``jax.lax.scan`` — one compiled block body regardless of depth (compile
time and HLO size stay flat across the 24..80-layer configs). ``cfg.remat``
wraps the block body in ``jax.checkpoint``.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import (AttnCache, attention_layer,
                                    init_attention, init_attn_cache)
from repro.models.layers import (_dtype, init_embeddings, init_mlp,
                                 init_rms_norm, embed, mlp, rms_norm,
                                 unembed)
from repro.parallel.axes import constrain, current_mesh

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_dense_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": init_attention(k1, cfg),
            "ln2": init_rms_norm(cfg.d_model),
            "ffn": init_mlp(k2, cfg)}


def _init_moe_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": init_attention(k1, cfg),
            "ln2": init_rms_norm(cfg.d_model),
            "ffn": moe_lib.init_moe(k2, cfg)}


def _init_ssm_block(key, cfg: ModelConfig) -> dict:
    return {"ln": init_rms_norm(cfg.d_model),
            "ssm": ssm_lib.init_ssm(key, cfg)}


def _init_encdec_dec_block(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": init_rms_norm(cfg.d_model),
            "self_attn": init_attention(k1, cfg),
            "ln2": init_rms_norm(cfg.d_model),
            "cross_attn": init_attention(k2, cfg, cross=True),
            "ln3": init_rms_norm(cfg.d_model),
            "ffn": init_mlp(k3, cfg, gated=False)}


def _init_enc_block(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": init_attention(k1, cfg, n_layers_scale=cfg.enc_layers),
            "ln2": init_rms_norm(cfg.d_model),
            "ffn": init_mlp(k2, cfg, gated=False)}


_BLOCK_INIT = {"dense": _init_dense_block, "vlm": _init_dense_block,
               "moe": _init_moe_block, "ssm": _init_ssm_block,
               "hybrid": _init_ssm_block, "audio": _init_encdec_dec_block}


def init_model_params(key, cfg: ModelConfig) -> dict:
    ke, kb, ks, kenc = jax.random.split(key, 4)
    block_init = _BLOCK_INIT[cfg.family]
    layer_keys = jax.random.split(kb, cfg.n_layers)
    blocks = jax.vmap(lambda k: block_init(k, cfg))(layer_keys)
    params = {"embed": init_embeddings(ke, cfg),
              "blocks": blocks,
              "final_norm": init_rms_norm(cfg.d_model)}
    if cfg.family == "hybrid":
        k1, k2 = jax.random.split(ks)
        params["shared"] = {"ln1": init_rms_norm(cfg.d_model),
                            "attn": init_attention(k1, cfg),
                            "ln2": init_rms_norm(cfg.d_model),
                            "ffn": init_mlp(k2, cfg)}
    if cfg.is_encdec:
        enc_keys = jax.random.split(kenc, cfg.enc_layers)
        params["enc_blocks"] = jax.vmap(
            lambda k: _init_enc_block(k, cfg))(enc_keys)
        params["enc_norm"] = init_rms_norm(cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# MoE ffn wrapper: token-local dispatch under shard_map on the mesh
# ---------------------------------------------------------------------------

def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            seq_sharded: bool) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux scalar).

    **Expert parallelism over the `model` axis** (all-to-all dispatch):

      * tokens stay on their (batch x seq) shard for routing — routing is
        per-token, so the dispatch buffers scale with the *local* token
        count (B_loc x S_loc), never the gathered sequence;
      * the model axis owns experts: with TP >= E each expert lives on
        dup = TP/E devices, each holding an F-slice of that expert
        ([E*dup, D, F/dup] EP layout, a free contiguous reshape of the
        stored [E, D(fsdp), F(tp)] weights); with E > TP each device owns
        E/TP whole experts;
      * one all-to-all sends each expert's token buffer to its owners
        (duplicated across F-slices), dense per-expert SwiGLU GEMMs run at
        full MXU tile sizes, and the return all-to-all brings partial
        outputs home where the dup F-slices are summed — completing the F
        contraction with *no* psum over model;
      * expert weights' fsdp (D-axis over `data`) shard is all-gathered at
        use, ZeRO-3 style.

    Wire per layer: 2 all-to-alls of dup * T_loc * k * capacity_factor * D
    — versus a gather-based TP dispatch this is ~T_loc*D*(2*dup*k*cf) vs
    T_full*D on wire, and 16x less live dispatch memory at TP=16.
    """
    b, s, d = x.shape
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.shape \
            or mesh.shape["model"] == 1:
        out, aux = moe_lib.moe_ffn_local(x.reshape(-1, d), p, cfg)
        return out.reshape(b, s, d), aux

    tp = mesh.shape["model"]
    e, f = cfg.n_experts, cfg.d_ff
    if tp % e == 0:
        dup, e_loc = tp // e, 1
    elif e % tp == 0:
        dup, e_loc = 1, e // tp
    else:
        raise ValueError(f"EP needs tp % E == 0 or E % tp == 0; "
                         f"got E={e}, tp={tp}")
    f_loc = f // dup

    # token sharding from the *active* rule set, divisibility-sanitised
    # (long-context decode has batch=1: batch stays unsharded there)
    from repro.parallel.axes import sanitized_spec
    x_spec = sanitized_spec(x.shape,
                            ("batch", "seq" if seq_sharded else None,
                             None))
    token_axes = tuple(a for part in x_spec if part
                       for a in ((part,) if isinstance(part, str)
                                 else part))
    all_axes = token_axes if token_axes else None

    # EP layout: [E, D, F] -> [E*dup, D, F/dup] (contiguous F split)
    def ep_in(w):                     # w1/w3: [E, D, F]
        return w.reshape(e, d, dup, f_loc).transpose(0, 2, 1, 3) \
                .reshape(e * dup, d, f_loc)

    def ep_out(w):                    # w2: [E, F, D]
        return w.reshape(e, dup, f_loc, d).reshape(e * dup, f_loc, d)

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)

    if s == 1:
        # ---- decode: weights-stationary TP-MoE (§Perf H1.2) ------------
        # At one token per sequence the ZeRO-3 weight gathers dwarf the
        # activations (~300 MB of expert weights vs ~100 KB of tokens per
        # layer, measured). Invert the movement: weights are used in their
        # *storage* sharding [E, D/data, F/model] — zero weight bytes on
        # the wire — while the tokens are all-gathered over the dp axes
        # (every device then holds the same tiny global batch). Each
        # device contracts its D-shard (psum over data) and F-shard
        # (psum over model); one final all-gather re-assembles D. Per
        # layer this moves a few MB instead of hundreds.
        d_data = mesh.shape.get("data", 1)
        d_shard = d // d_data
        # batch may be too small to shard (long_500k: batch 1)
        from repro.parallel.axes import sanitized_spec
        xd_spec = sanitized_spec(x.shape, ("batch", None, None))
        part0 = xd_spec[0]
        gather_axes = (() if part0 is None
                       else ((part0,) if isinstance(part0, str)
                             else tuple(part0)))

        def local_dec(x_loc, router, w1, w3, w2):
            # x_loc [B_loc, 1, D]; w1/w3 [E, D/data, F/model];
            # w2 [E, F/model, D/data]
            bl = x_loc.shape[0]
            x_all = x_loc.reshape(bl, d)
            for ax in gather_axes:
                x_all = jax.lax.all_gather(x_all, ax, axis=0, tiled=True)
            buf, meta, aux = moe_lib.route_and_dispatch(x_all, router, cfg)
            # D contraction over the data axis
            if "data" in mesh.shape:
                lo = jax.lax.axis_index("data") * d_shard
                buf_d = jax.lax.dynamic_slice_in_dim(buf, lo, d_shard,
                                                     axis=2)
            else:
                buf_d = buf
            cdt2 = w1.dtype
            h1 = jnp.einsum("ecd,edf->ecf", buf_d.astype(cdt2), w1)
            h3 = jnp.einsum("ecd,edf->ecf", buf_d.astype(cdt2), w3)
            if "data" in mesh.shape:
                h1 = jax.lax.psum(h1, "data")
                h3 = jax.lax.psum(h3, "data")
            hh = jax.nn.silu(h1) * h3                  # [E, cap, F/model]
            out_p = jnp.einsum("ecf,efd->ecd", hh, w2)  # [E,cap,D/data]
            out_p = jax.lax.psum(out_p, "model")        # finish F
            if "data" in mesh.shape:
                out_buf = jax.lax.all_gather(out_p, "data", axis=2,
                                             tiled=True)
            else:
                out_buf = out_p
            y_all = moe_lib.combine(out_buf.astype(buf.dtype), meta, d,
                                    cfg)                # [T_all, D]
            off = jnp.zeros((), jnp.int32)
            for ax in gather_axes:
                off = off * mesh.shape[ax] + jax.lax.axis_index(ax)
            y = jax.lax.dynamic_slice_in_dim(y_all, off * bl, bl, axis=0)
            return y.reshape(bl, 1, d), aux

        out, aux = jax.shard_map(
            local_dec, mesh=mesh,
            in_specs=(xd_spec, P(None, None),
                      P(None, "data", "model"), P(None, "data", "model"),
                      P(None, "model", "data")),
            out_specs=(xd_spec, P()),
            check_vma=False,
        )(x, p["router"], p["w1"], p["w3"], p["w2"])
        return out, aux

    def local(x_loc, router, w1, w3, w2):
        # fsdp gather of this device's expert(-slice) weights (ZeRO-3)
        w1f = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
        w3f = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
        w2f = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        bl, sl, _ = x_loc.shape
        buf, meta, aux = moe_lib.route_and_dispatch(
            x_loc.reshape(-1, d), router, cfg)          # [E, cap, D]
        cap = buf.shape[1]

        # pack destinations: expert e -> devices [e*dup, (e+1)*dup)
        if dup > 1:
            send = jnp.broadcast_to(buf[:, None], (e, dup, cap, d)) \
                      .reshape(tp, cap, d)
        else:
            send = buf.reshape(tp, e_loc * cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: [tp(sources), cap', d] — this device's expert(-slice)
        if e_loc > 1:
            # sources sent [e_loc, cap, d] chunks; regroup per expert
            xin = recv.reshape(tp, e_loc, cap, d).transpose(1, 0, 2, 3) \
                      .reshape(e_loc, tp * cap, d)
        else:
            xin = recv.reshape(1, tp * cap, d)
        out_e = moe_lib.expert_gemms(xin, w1f, w3f, w2f, cfg)
        if e_loc > 1:
            back = out_e.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3) \
                        .reshape(tp, e_loc * cap, d)
        else:
            back = out_e.reshape(tp, cap, d)
        ret = jax.lax.all_to_all(back, "model", split_axis=0,
                                 concat_axis=0, tiled=False)
        if dup > 1:                    # sum the F-slice partials
            out_buf = jnp.sum(ret.reshape(e, dup, cap, d)
                              .astype(jnp.float32), axis=1) \
                         .astype(ret.dtype)
        else:
            out_buf = ret.reshape(e, cap, d)
        y = moe_lib.combine(out_buf, meta, d, cfg)
        if all_axes:
            aux = jax.lax.pmean(aux, all_axes)
        return y.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], ep_in(p["w1"]), ep_in(p["w3"]), ep_out(p["w2"]))
    return out, aux


# ---------------------------------------------------------------------------
# block bodies
# ---------------------------------------------------------------------------

class BlockIO(NamedTuple):
    h: jax.Array
    aux: jax.Array                       # accumulated moe aux loss
    shared_cache: Any                    # hybrid: stacked shared-attn caches
    app_idx: jax.Array                   # hybrid: next shared-attn slot


def _attn_ffn_block(h, bp, cfg: ModelConfig, *, cache, positions,
                    seq_sharded, return_kv=False):
    resid = h
    x = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
    attn_out, new_cache = attention_layer(x, bp["attn"], cfg, causal=True,
                                          cache=cache, positions=positions,
                                          return_kv=return_kv)
    h = resid + attn_out
    h = constrain(h, "batch", "seq" if seq_sharded else None, None)
    resid = h
    x = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
    if cfg.n_experts:
        ffn_out, aux = moe_ffn(x, bp["ffn"], cfg, seq_sharded)
    else:
        ffn_out, aux = mlp(x, bp["ffn"], cfg), jnp.zeros((), jnp.float32)
    h = resid + ffn_out
    h = constrain(h, "batch", "seq" if seq_sharded else None, None)
    return h, aux, new_cache


def _ssm_block(h, bp, cfg: ModelConfig, *, cache, seq_sharded):
    resid = h
    x = rms_norm(h, bp["ln"]["scale"], cfg.norm_eps)
    out, new_cache = ssm_lib.ssm_block(x, bp["ssm"], cfg, cache=cache)
    h = resid + out
    h = constrain(h, "batch", "seq" if seq_sharded else None, None)
    return h, new_cache


# ---------------------------------------------------------------------------
# decoder-only forward (dense / vlm / moe / ssm / hybrid)
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def decoder_forward(params: dict, h: jax.Array, cfg: ModelConfig, *,
                    caches: Optional[dict] = None,
                    positions: Optional[jax.Array] = None,
                    seq_sharded: bool = True,
                    collect: bool = False):
    """Run the stacked decoder blocks. h: [B, S, D] embedded input.

    caches: per-family pytree with leaves stacked on a leading layer axis
    (see `model.init_cache`). Returns (h, aux, new_caches).

    ``collect=True`` (prefill): run full-sequence and additionally return
    the per-layer cache material (projected K/V; SSM conv/recurrent state).
    """
    decode = caches is not None
    fam = cfg.family

    if fam in ("dense", "vlm", "moe"):
        def body(carry, xs):
            h, aux = carry
            bp, cache = xs
            h, a, new_cache = _attn_ffn_block(
                h, bp, cfg, cache=cache if decode else None,
                positions=positions, seq_sharded=seq_sharded,
                return_kv=collect)
            return (h, aux + a), new_cache

        xs = (params["blocks"],
              caches["attn"] if decode else _dummy_layer_xs(cfg))
        (h, aux), new_attn = jax.lax.scan(_remat(body, cfg), (h, 0.0), xs)
        new_caches = ({"attn": new_attn} if (decode or collect) else None)
        return h, aux, new_caches

    if fam == "ssm":
        def body(carry, xs):
            h = carry
            bp, cache = xs
            if collect:
                resid = h
                x = rms_norm(h, bp["ln"]["scale"], cfg.norm_eps)
                out, new_cache = ssm_lib.ssm_prefill_with_cache(
                    x, bp["ssm"], cfg)
                h = resid + out
                h = constrain(h, "batch",
                              "seq" if seq_sharded else None, None)
            else:
                h, new_cache = _ssm_block(h, bp, cfg,
                                          cache=cache if decode else None,
                                          seq_sharded=seq_sharded)
            return h, new_cache

        xs = (params["blocks"],
              caches["ssm"] if decode else _dummy_layer_xs(cfg))
        h, new_ssm = jax.lax.scan(_remat(body, cfg), h, xs)
        new_caches = ({"ssm": new_ssm} if (decode or collect) else None)
        return h, jnp.zeros((), jnp.float32), new_caches

    if fam == "hybrid":
        # static grouping: every `every` SSM layers, one weight-shared
        # attention block (own KV cache per application). Python loop over
        # groups keeps cache plumbing static; inner scans keep HLO small.
        every = cfg.hybrid_attn_every
        n_apps = cfg.n_layers // every
        shared = params["shared"]
        aux = jnp.zeros((), jnp.float32)

        def ssm_body(carry, xs):
            h = carry
            bp, cache = xs
            if collect:
                resid = h
                x = rms_norm(h, bp["ln"]["scale"], cfg.norm_eps)
                out, new_cache = ssm_lib.ssm_prefill_with_cache(
                    x, bp["ssm"], cfg)
                h = resid + out
                h = constrain(h, "batch",
                              "seq" if seq_sharded else None, None)
            else:
                h, new_cache = _ssm_block(h, bp, cfg,
                                          cache=cache if decode else None,
                                          seq_sharded=seq_sharded)
            return h, new_cache

        def shared_attn_block(h, cache_a):
            resid = h
            x = rms_norm(h, shared["ln1"]["scale"], cfg.norm_eps)
            a_out, new_attn_c = attention_layer(
                x, shared["attn"], cfg, causal=True, cache=cache_a,
                positions=positions, return_kv=collect)
            h = resid + a_out
            resid = h
            x = rms_norm(h, shared["ln2"]["scale"], cfg.norm_eps)
            h = resid + mlp(x, shared["ffn"], cfg)
            h = constrain(h, "batch",
                          "seq" if seq_sharded else None, None)
            return h, new_attn_c

        if not decode:
            # remat each shared-attn application (19 un-rematted
            # full-sequence attention blocks dominate zamba2's train
            # memory otherwise)
            shared_attn_block = _remat(shared_attn_block, cfg)

        def run_group(h, lo, hi, app_idx):
            bp_g = jax.tree.map(lambda a: a[lo:hi], params["blocks"])
            cache_g = (jax.tree.map(lambda a: a[lo:hi], caches["ssm"])
                       if decode else jnp.zeros((hi - lo,), jnp.float32))
            h, new_ssm = jax.lax.scan(_remat(ssm_body, cfg), h,
                                      (bp_g, cache_g))
            new_attn_c = None
            if app_idx is not None:
                cache_a = (jax.tree.map(lambda a: a[app_idx],
                                        caches["attn"]) if decode else None)
                h, new_attn_c = shared_attn_block(h, cache_a)
            return h, new_ssm, new_attn_c

        new_ssm_parts, new_attn_parts = [], []
        for g in range(n_apps):
            h, ssm_c, attn_c = run_group(h, g * every, (g + 1) * every, g)
            new_ssm_parts.append(ssm_c)
            new_attn_parts.append(attn_c)
        if n_apps * every < cfg.n_layers:         # trailing layers
            h, ssm_c, _ = run_group(h, n_apps * every, cfg.n_layers, None)
            new_ssm_parts.append(ssm_c)

        new_caches = None
        if decode or collect:
            new_ssm = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *new_ssm_parts)
            new_attn = jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=0), *new_attn_parts)
            new_caches = {"ssm": new_ssm, "attn": new_attn}
        return h, aux, new_caches

    raise ValueError(f"decoder_forward: unsupported family {fam}")


def _dummy_layer_xs(cfg: ModelConfig):
    """Per-layer scan placeholder when no caches flow through."""
    return jnp.zeros((cfg.n_layers,), jnp.float32)


# ---------------------------------------------------------------------------
# encoder (whisper) and enc-dec forward
# ---------------------------------------------------------------------------

def encoder_forward(params: dict, h: jax.Array, cfg: ModelConfig, *,
                    seq_sharded: bool = False) -> jax.Array:
    def body(carry, bp):
        h = carry
        resid = h
        x = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
        a_out, _ = attention_layer(x, bp["attn"], cfg, causal=False,
                                   use_rope=False)
        h = resid + a_out
        resid = h
        x = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
        h = resid + mlp(x, bp["ffn"], cfg, act="gelu")
        h = constrain(h, "batch", None, None)
        return h, None

    h, _ = jax.lax.scan(_remat(body, cfg), h, params["enc_blocks"])
    return rms_norm(h, params["enc_norm"]["scale"], cfg.norm_eps)


def encdec_decoder_forward(params: dict, h: jax.Array, cfg: ModelConfig, *,
                           enc_out: Optional[jax.Array] = None,
                           caches: Optional[dict] = None,
                           positions: Optional[jax.Array] = None,
                           seq_sharded: bool = True,
                           collect: bool = False):
    """Whisper decoder. Training: cross-attn K/V computed per block from
    ``enc_out``. Decode: cross K/V come precomputed from the cache.
    ``collect=True`` (prefill): also return per-layer self K/V + cross K/V."""
    decode = caches is not None
    cdt = _dtype(cfg.dtype)

    def body(carry, xs):
        h, aux = carry
        bp, self_cache, cross_k, cross_v = xs
        resid = h
        x = rms_norm(h, bp["ln1"]["scale"], cfg.norm_eps)
        a_out, new_self = attention_layer(
            x, bp["self_attn"], cfg, causal=True,
            cache=self_cache if decode else None, positions=positions,
            return_kv=collect)
        h = resid + a_out
        resid = h
        x = rms_norm(h, bp["ln2"]["scale"], cfg.norm_eps)
        if decode:
            ck, cv = cross_k, cross_v
        else:
            ck = jnp.einsum("bsd,dgk->bsgk", enc_out,
                            bp["cross_attn"]["wk"].astype(cdt))
            cv = jnp.einsum("bsd,dgk->bsgk", enc_out,
                            bp["cross_attn"]["wv"].astype(cdt))
        c_out, _ = attention_layer(x, bp["cross_attn"], cfg,
                                   cross_kv=(ck, cv))
        h = resid + c_out
        resid = h
        x = rms_norm(h, bp["ln3"]["scale"], cfg.norm_eps)
        h = resid + mlp(x, bp["ffn"], cfg, act="gelu")
        h = constrain(h, "batch", "seq" if (seq_sharded and not decode)
                      else None, None)
        out = (new_self, ck, cv) if collect else new_self
        return (h, aux), out

    if decode:
        xs = (params["blocks"], caches["self"],
              caches["cross_k"], caches["cross_v"])
    else:
        n_l = cfg.n_layers
        xs = (params["blocks"], _dummy(n_l), _dummy(n_l), _dummy(n_l))
    (h, aux), scanned = jax.lax.scan(_remat(body, cfg), (h, 0.0), xs)
    new_caches = None
    if decode:
        new_caches = {"self": scanned, "cross_k": caches["cross_k"],
                      "cross_v": caches["cross_v"]}
    elif collect:
        new_self, cross_k, cross_v = scanned
        new_caches = {"self": new_self, "cross_k": cross_k,
                      "cross_v": cross_v}
    return h, aux, new_caches


def _dummy(n):
    return jnp.zeros((n,), jnp.float32)
