"""Unified LM covering all assigned families (dense/MoE/hybrid/SSM/VLM/
audio) with scan-over-layers + remat — compile cost independent of depth,
which is what makes 61–72-layer trillion-parameter dry-runs feasible.

Families map to scan templates:
* dense / moe / vlm / audio — homogeneous decoder layers, one scan over
  the stacked [L, ...] params; per-layer static flags (gemma3's 5:1
  local:global pattern) ride along as scanned xs.
* hybrid (jamba) — scan over *periods* of ``attn_every`` layers; the
  period body unrolls 1 attention + (N-1) Mamba sublayers with the
  dense/MoE FFN alternation baked into the template.
* ssm (xlstm) — scan over periods of ``slstm_every`` blocks: (N-1)
  stacked mLSTM + 1 sLSTM.

Serving: ``init_cache`` + ``decode_step`` implement one-token decode with
per-family persistent state (KV caches / Mamba (h, conv) / mLSTM (C,n,m)).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import mamba as mam
from . import moe as moe_mod
from . import xlstm as xl
from .layers import (attention_block, attention_block_params,
                     attention_decode_block, cross_entropy_loss, mlp_params,
                     rms_norm, swiglu)


# ---------------------------------------------------------------------------
# parameter init
def _layer_params(key, cfg: ModelConfig, moe_layer: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": attention_block_params(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            cfg.dtype, qk_norm=cfg.attention.qk_norm),
    }
    if moe_layer:
        p["moe"] = moe_mod.moe_params(k2, cfg.d_model, cfg.d_ff,
                                      cfg.moe.num_experts, cfg.dtype)
    elif cfg.d_ff:
        p["mlp"] = mlp_params(k3, cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def _mamba_layer_params(key, cfg: ModelConfig, moe_layer: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        "mamba": mam.mamba_params(k1, cfg.d_model, cfg.mamba, cfg.dtype),
    }
    if moe_layer:
        p["moe"] = moe_mod.moe_params(k2, cfg.d_model, cfg.d_ff,
                                      cfg.moe.num_experts, cfg.dtype)
    elif cfg.d_ff:
        p["mlp"] = mlp_params(k3, cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def _stack(key, n, fn):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def _is_moe_layer(cfg: ModelConfig, i: int) -> bool:
    return (cfg.moe is not None
            and i % cfg.moe.every_n_layers == cfg.moe.every_n_layers - 1)


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kh, kl = jax.random.split(key, 3)
    s = 0.02
    params: dict = {"final_norm": jnp.ones((cfg.d_model,), cfg.dtype)}
    V = cfg.padded_vocab   # §Perf: shardable padded vocab (base.py)
    if cfg.num_codebooks:
        params["embed"] = (jax.random.normal(
            ke, (cfg.num_codebooks, V, cfg.d_model)) * s
        ).astype(cfg.dtype)
        params["head"] = (jax.random.normal(
            kh, (cfg.d_model, cfg.num_codebooks * V)) * s
        ).astype(cfg.dtype)
    else:
        params["embed"] = (jax.random.normal(
            ke, (V, cfg.d_model)) * s).astype(cfg.dtype)
        if not cfg.tie_embeddings:
            params["head"] = (jax.random.normal(
                kh, (cfg.d_model, V)) * s).astype(cfg.dtype)

    if cfg.family == "ssm":
        x = cfg.xlstm
        n_periods = cfg.n_layers // x.slstm_every
        n_m = x.slstm_every - 1
        k1, k2 = jax.random.split(kl)
        params["layers"] = {
            "mlstm": _stack(k1, n_periods, lambda k: _stack(
                k, n_m, lambda kk: xl.mlstm_params(
                    kk, cfg.d_model, cfg.n_heads, x, cfg.dtype))),
            "slstm": _stack(k2, n_periods, lambda k: xl.slstm_params(
                k, cfg.d_model, cfg.n_heads, cfg.dtype)),
        }
    elif cfg.family == "hybrid":
        period = cfg.attention.attn_every
        n_periods = cfg.n_layers // period
        ks = jax.random.split(kl, period)
        stacked = {}
        for pos in range(period):
            moe_l = cfg.moe is not None and pos % cfg.moe.every_n_layers \
                == cfg.moe.every_n_layers - 1
            if pos == 0:
                stacked[f"pos{pos}"] = _stack(
                    ks[pos], n_periods,
                    lambda k, m=moe_l: _layer_params(k, cfg, m))
            else:
                stacked[f"pos{pos}"] = _stack(
                    ks[pos], n_periods,
                    lambda k, m=moe_l: _mamba_layer_params(k, cfg, m))
        params["layers"] = stacked
    else:
        moe_l = cfg.moe is not None and cfg.moe.every_n_layers == 1
        if cfg.moe is not None and cfg.moe.every_n_layers > 1:
            # alternating moe/dense: scan over pairs
            n_pairs = cfg.n_layers // cfg.moe.every_n_layers
            k1, k2 = jax.random.split(kl)
            params["layers"] = {
                "dense": _stack(k1, n_pairs,
                                lambda k: _layer_params(k, cfg, False)),
                "moe": _stack(k2, n_pairs,
                              lambda k: _layer_params(k, cfg, True)),
            }
        else:
            params["layers"] = _stack(
                kl, cfg.n_layers, lambda k: _layer_params(k, cfg, moe_l))
    return params


def abstract_params(cfg: ModelConfig) -> dict:
    """Parameter ShapeDtypeStructs without any allocation (dry-run). The
    key is made inside the trace: a concrete one would run two small
    programs on the default device, which the host-side gate must not."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


# ---------------------------------------------------------------------------
# forward
def _global_flags(cfg: ModelConfig) -> jnp.ndarray | None:
    ge = cfg.attention.global_every
    if ge is None:
        return None
    return jnp.array([(i % ge) == ge - 1 for i in range(cfg.n_layers)])


# Named scopes (``jax.named_scope``) label the step's layers in the
# compiled program's ``op_name`` metadata, so a device trace's time can be
# put down to them; they add no operation. A scope name must not contain
# ``transpose`` or ``backward``: the estimator reads those in the name
# stack as the backward pass (``core/analyzer.py:_BWD_MARKERS``).
def _decoder_layer(x, lp, cfg: ModelConfig, *, is_global=None,
                   positions=None):
    window = cfg.attention.sliding_window
    with jax.named_scope("attn"):
        h, _ = attention_block(
            rms_norm(x, lp["ln1"]), lp["attn"], cfg.attention, cfg.n_heads,
            cfg.n_kv_heads, cfg.hd, positions=positions,
            is_global=is_global, window=window)
        x = x + h
    return _ffn(x, lp, cfg)


def _ffn(x, lp, cfg: ModelConfig):
    """The layer's feed-forward half: pre-norm, MoE or gated MLP, and the
    residual add."""
    with jax.named_scope("moe" if "moe" in lp else "mlp"):
        xn = rms_norm(x, lp["ln2"])
        if "moe" in lp:
            x = x + moe_mod.moe_ffn(xn, lp["moe"], cfg.moe)
        elif "mlp" in lp:
            x = x + swiglu(xn, **lp["mlp"])
        return x


def _mamba_layer(x, lp, cfg: ModelConfig):
    h, _ = mam.mamba_block(rms_norm(x, lp["ln1"]), lp["mamba"], cfg.mamba)
    return _ffn(x + h, lp, cfg)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def backbone(params, x, cfg: ModelConfig, positions=None):
    """x: [B, S, D] embedded inputs -> final hidden states."""
    if cfg.family == "ssm":
        xcfg = cfg.xlstm

        def period_body(h, pp):
            def m_body(hh, mp):
                return hh + xl.mlstm_block(hh, mp, cfg.n_heads, xcfg), None
            h, _ = jax.lax.scan(_remat(m_body, cfg), h, pp["mlstm"])
            s_out, _ = xl.slstm_block(h, pp["slstm"], cfg.n_heads,
                                      chunk=xcfg.chunk)
            return h + s_out, None

        x, _ = jax.lax.scan(_remat(period_body, cfg), x, params["layers"])
    elif cfg.family == "hybrid":
        period = cfg.attention.attn_every

        def period_body(h, pp):
            h = _decoder_layer(h, pp["pos0"], cfg, positions=positions)
            for pos in range(1, period):
                h = _mamba_layer(h, pp[f"pos{pos}"], cfg)
            return h, None

        x, _ = jax.lax.scan(_remat(period_body, cfg), x, params["layers"])
    elif cfg.moe is not None and cfg.moe.every_n_layers > 1:
        def pair_body(h, pp):
            h = _decoder_layer(h, pp["dense"], cfg, positions=positions)
            h = _decoder_layer(h, pp["moe"], cfg, positions=positions)
            return h, None

        x, _ = jax.lax.scan(_remat(pair_body, cfg), x, params["layers"])
    else:
        flags = _global_flags(cfg)
        xs = (params["layers"], flags) if flags is not None \
            else (params["layers"],)

        def body(h, inp):
            lp = inp[0]
            ig = inp[1] if len(inp) > 1 else None
            return _decoder_layer(h, lp, cfg, is_global=ig,
                                  positions=positions), None

        x, _ = jax.lax.scan(_remat(body, cfg), x, xs)
    return rms_norm(x, params["final_norm"])


def embed_inputs(params, batch: dict, cfg: ModelConfig):
    """Family-specific input embedding. Modality frontends are stubs:
    VLM patch embeddings / audio EnCodec tokens arrive precomputed."""
    with jax.named_scope("embed"):
        return _embed(params, batch, cfg)


def _embed(params, batch: dict, cfg: ModelConfig):
    if cfg.family == "vlm":
        text = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(cfg.dtype), text], axis=1)
        return x
    if cfg.family == "audio":
        # sum of per-codebook embeddings (delay pattern applied upstream)
        emb = jax.vmap(lambda cb, tok: jnp.take(cb, tok, axis=0),
                       in_axes=(0, 2), out_axes=2)(
            params["embed"], batch["codes"])      # [B,S,K,D]
        return emb.sum(axis=2)
    return jnp.take(params["embed"], batch["tokens"], axis=0)


def logits_fn(params, h, cfg: ModelConfig):
    from ..distributed.act_sharding import constrain
    if cfg.tie_embeddings:
        if cfg.num_codebooks:
            return jnp.einsum("bsd,kvd->bskv", h, params["embed"])
        return constrain(h @ params["embed"].T, ("batch", None, "vocab"))
    if cfg.num_codebooks:
        B, S, D = h.shape
        out = constrain(h @ params["head"], ("batch", None, "vocab"))
        return out.reshape(B, S, cfg.num_codebooks, cfg.padded_vocab)
    return constrain(h @ params["head"], ("batch", None, "vocab"))


def loss_fn(params, batch: dict, cfg: ModelConfig):
    x = embed_inputs(params, batch, cfg)
    positions = jnp.arange(x.shape[1])
    h = backbone(params, x, cfg, positions=positions)
    with jax.named_scope("head_loss"):
        if cfg.family == "vlm":
            h = h[:, batch["patch_embeds"].shape[1]:]  # loss on text
        logits = logits_fn(params, h, cfg)
        return cross_entropy_loss(logits, batch["labels"])


# ---------------------------------------------------------------------------
# serving: cache init + one-token decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Persistent decode state, family-specific."""
    kv = lambda: jnp.zeros(  # noqa: E731
        (batch, max_len, cfg.n_kv_heads, cfg.hd), cfg.dtype)
    if cfg.family == "ssm":
        x = cfg.xlstm
        n_periods = cfg.n_layers // x.slstm_every
        n_m = x.slstm_every - 1
        dv = cfg.d_model // cfg.n_heads
        dk = max(int(dv * x.qk_dim_factor), 8)
        return {
            "mlstm_C": jnp.zeros((n_periods, n_m, batch, cfg.n_heads,
                                  dk, dv), jnp.float32),
            "mlstm_n": jnp.zeros((n_periods, n_m, batch, cfg.n_heads, dk),
                                 jnp.float32),
            "mlstm_m": jnp.full((n_periods, n_m, batch, cfg.n_heads),
                                -1e30, jnp.float32),
            "slstm": jnp.zeros((n_periods, 4, batch, cfg.d_model),
                               jnp.float32),
        }
    if cfg.family == "hybrid":
        period = cfg.attention.attn_every
        n_periods = cfg.n_layers // period
        m = cfg.mamba
        d_inner = m.expand * cfg.d_model
        return {
            "k": jnp.zeros((n_periods, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
            "v": jnp.zeros((n_periods, batch, max_len, cfg.n_kv_heads,
                            cfg.hd), cfg.dtype),
            "mamba_h": jnp.zeros((n_periods, period - 1, batch, d_inner,
                                  m.d_state), jnp.float32),
            "mamba_conv": jnp.zeros((n_periods, period - 1, batch,
                                     m.d_conv - 1, d_inner), jnp.float32),
        }
    L = cfg.n_layers
    return {
        "k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       cfg.dtype),
        "v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.hd),
                       cfg.dtype),
    }


def decode_step(params, cache: dict, batch: dict, cache_len: int,
                cfg: ModelConfig):
    """One new token for every sequence. Returns (logits, new_cache)."""
    if cfg.family == "vlm":
        # image patches were consumed at prefill; decode is text-only
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    else:
        x = embed_inputs(params, batch, cfg)       # [B, 1, D]
    window = cfg.attention.sliding_window

    if cfg.family == "ssm":
        xcfg = cfg.xlstm

        def period_body(h, st):
            def m_body(hh, mst):
                mp, (C, n, m) = mst
                out, (C2, n2, m2) = xl.mlstm_decode_step(
                    hh, (C, n, m), mp, cfg.n_heads)
                return hh + out, (C2, n2, m2)
            h, new_m = jax.lax.scan(
                m_body, h, (st["p"]["mlstm"],
                            (st["C"], st["n"], st["m"])))
            hs, cs, ns, ms = st["slstm"]
            s_out, sstate = xl.slstm_block(
                h, st["p"]["slstm"], cfg.n_heads, chunk=1,
                state=(hs, cs, ns, ms))
            return h + s_out, {"m": new_m, "s": jnp.stack(sstate)}

        def outer(h, st):
            return period_body(h, st)

        h, news = jax.lax.scan(
            outer, x,
            {"p": params["layers"],
             "C": cache["mlstm_C"], "n": cache["mlstm_n"],
             "m": cache["mlstm_m"],
             "slstm": cache["slstm"]})
        new_cache = {
            "mlstm_C": news["m"][0], "mlstm_n": news["m"][1],
            "mlstm_m": news["m"][2], "slstm": news["s"],
        }
    elif cfg.family == "hybrid":
        period = cfg.attention.attn_every

        def period_body(h, st):
            pp = st["p"]
            hn = rms_norm(h, pp["pos0"]["ln1"])
            a_out, ck, cv = attention_decode_block(
                hn, pp["pos0"]["attn"], cfg.attention, cfg.n_heads,
                cfg.n_kv_heads, cfg.hd, st["k"], st["v"], cache_len,
                window=window)
            h = h + a_out
            xn = rms_norm(h, pp["pos0"]["ln2"])
            if "moe" in pp["pos0"]:
                h = h + moe_mod.moe_ffn(xn, pp["pos0"]["moe"],
                                        _decode_moe(cfg))
            elif "mlp" in pp["pos0"]:
                h = h + swiglu(xn, **pp["pos0"]["mlp"])
            new_h, new_conv = [], []
            for pos in range(1, period):
                lp = pp[f"pos{pos}"]
                m_out, mstate = mam.mamba_decode_step(
                    rms_norm(h, lp["ln1"]),
                    {"h": st["mh"][pos - 1], "conv": st["mc"][pos - 1]},
                    lp["mamba"], cfg.mamba)
                h = h + m_out
                xn = rms_norm(h, lp["ln2"])
                if "moe" in lp:
                    h = h + moe_mod.moe_ffn(xn, lp["moe"], _decode_moe(cfg))
                elif "mlp" in lp:
                    h = h + swiglu(xn, **lp["mlp"])
                new_h.append(mstate["h"])
                new_conv.append(mstate["conv"])
            return h, {"k": ck, "v": cv, "mh": jnp.stack(new_h),
                       "mc": jnp.stack(new_conv)}

        h, news = jax.lax.scan(
            period_body, x,
            {"p": params["layers"], "k": cache["k"], "v": cache["v"],
             "mh": cache["mamba_h"], "mc": cache["mamba_conv"]})
        new_cache = {"k": news["k"], "v": news["v"],
                     "mamba_h": news["mh"], "mamba_conv": news["mc"]}
    else:
        flags = _global_flags(cfg)

        def body(h, st):
            lp = st["p"]
            ig = st.get("g")
            hn = rms_norm(h, lp["ln1"])
            a_out, ck, cv = attention_decode_block(
                hn, lp["attn"], cfg.attention, cfg.n_heads, cfg.n_kv_heads,
                cfg.hd, st["k"], st["v"], cache_len, window=window,
                is_global=ig)
            h = h + a_out
            xn = rms_norm(h, lp["ln2"])
            if "moe" in lp:
                h = h + moe_mod.moe_ffn(xn, lp["moe"], _decode_moe(cfg))
            elif "mlp" in lp:
                h = h + swiglu(xn, **lp["mlp"])
            return h, {"k": ck, "v": cv}

        layers = params["layers"]
        if cfg.moe is not None and cfg.moe.every_n_layers > 1:
            def pair_body(h, st):
                h, kv1 = body(h, {"p": st["pd"], "k": st["k1"],
                                  "v": st["v1"]})
                h, kv2 = body(h, {"p": st["pm"], "k": st["k2"],
                                  "v": st["v2"]})
                return h, {"k": jnp.stack([kv1["k"], kv2["k"]]),
                           "v": jnp.stack([kv1["v"], kv2["v"]])}
            n_pairs = cache["k"].shape[0] // 2
            kp = cache["k"].reshape((n_pairs, 2) + cache["k"].shape[1:])
            vp = cache["v"].reshape((n_pairs, 2) + cache["v"].shape[1:])
            h, news = jax.lax.scan(
                pair_body, x,
                {"pd": layers["dense"], "pm": layers["moe"],
                 "k1": kp[:, 0], "v1": vp[:, 0],
                 "k2": kp[:, 1], "v2": vp[:, 1]})
            nk = news["k"].reshape(cache["k"].shape)
            nv = news["v"].reshape(cache["v"].shape)
            new_cache = {"k": nk, "v": nv}
        else:
            xs = {"p": layers, "k": cache["k"], "v": cache["v"]}
            if flags is not None:
                xs["g"] = flags
            h, news = jax.lax.scan(body, x, xs)
            new_cache = {"k": news["k"], "v": news["v"]}

    h = rms_norm(h, params["final_norm"])
    return logits_fn(params, h, cfg), new_cache


def _decode_moe(cfg: ModelConfig):
    return dataclasses.replace(cfg.moe, num_groups=1)
