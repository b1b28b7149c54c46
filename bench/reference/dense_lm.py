"""Plain reference of a dense decoder's training step.

Straightforward ``jax.numpy`` that imports nothing of the program: token
embedding, per layer RMS norm -> grouped-query attention with rotary
positions (split halves) -> residual -> RMS norm -> gated MLP
(``silu(x W_gate) * (x W_up) W_down``) -> residual, a final RMS norm,
the output head (the embedding's transpose when tied), and the mean
token cross-entropy. Training: gradients of that loss, clipped to a
global norm, then AdamW.

Every operation runs in float32, every matrix product at
``Precision.HIGHEST``. What the configuration states about storage is
kept: weights and gradients are stored in its ``param_dtype``, Adam's
moments in float32. Attention is taken over blocks of queries and the
loss over blocks of positions, each under ``jax.checkpoint``, and every
layer is rematerialized, so that the reference fits beside nothing else
on one chip at the timed sizes.

``precision="fp8"`` is the control: the same step with every matrix
product in float8, the nearest precision below the configuration's
bfloat16, as fp8 training does it: in the forward pass both operands
are rounded to e4m3 under a per-tensor scale, and in the backward pass
the incoming gradient is rounded to e5m2 under a per-tensor scale and
multiplied with the rounded operands. ``fault="half_batch"`` takes the
loss over the first half of the batch's rows (of its positions where
the batch has one row).
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from .. import weights as W

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3, E5M2 = jnp.float8_e4m3fn, jnp.float8_e5m2
Q_BLOCK = 512       # queries per attention block
LOSS_BLOCK = 512    # positions per loss block


def _round(x, dtype):
    """Round ``x`` to the float8 ``dtype`` under a per-tensor scale."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return _einsum(spec, _round(a, E4M3), _round(b, E4M3))


def _mm_fp8_fwd(spec, a, b):
    aq, bq = _round(a, E4M3), _round(b, E4M3)
    return _einsum(spec, aq, bq), (aq, bq)


def _mm_fp8_bwd(spec, res, ct):
    _, vjp = jax.vjp(partial(_einsum, spec), *res)
    return vjp(_round(ct, E5M2))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec, a, b, precision):
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        return _mm_fp8(spec, a, b)
    return _einsum(spec, a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, theta):
    """x: [B, S, ..., hd]; rotate the two halves of the head dimension."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    ang = ang.reshape((1, s) + (1,) * (x.ndim - 3) + (hd // 2,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s_ = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], axis=-1)


def attention(x, lw, cfg, precision):
    b, s, _ = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g = h // kv
    theta = cfg["rope_theta"]
    q = rope(_mm("bsd,de->bse", x, lw["wq"], precision)
             .reshape(b, s, kv, g, hd), theta)
    k = rope(_mm("bsd,de->bse", x, lw["wk"], precision)
             .reshape(b, s, kv, hd), theta)
    v = _mm("bsd,de->bse", x, lw["wv"], precision).reshape(b, s, kv, hd)
    qb_len = min(Q_BLOCK, s)
    if s % qb_len:
        raise ValueError(f"sequence {s} is not a multiple of {qb_len}")
    nq = s // qb_len

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * qb_len, qb_len, axis=1)
        sc = _mm("bqhgd,bkhd->bhgqk", qb, k, precision) / math.sqrt(hd)
        qpos = i * qb_len + jnp.arange(qb_len)
        causal = qpos[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return _mm("bhgqk,bkhd->bqhgd", p, v, precision)

    o = jax.lax.map(block, jnp.arange(nq))          # [nq, B, Q, kv, g, hd]
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h * hd)
    return _mm("bse,ed->bsd", o, lw["wo"], precision)


def mlp(x, lw, precision):
    gate = _mm("bsd,df->bsf", x, lw["w_gate"], precision)
    up = _mm("bsd,df->bsf", x, lw["w_up"], precision)
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"],
               precision)


def layer(x, lw, cfg, precision):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["ln1"], eps), lw["attn"], cfg,
                      precision)
    return x + mlp(rms_norm(x, lw["ln2"], eps), lw["mlp"], precision)


def loss_fn(params, tokens, labels, cfg, precision="f32", fault=None):
    """Mean next-token cross-entropy of ``tokens`` against ``labels``."""
    if fault == "half_batch":
        if tokens.shape[0] >= 2:
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        else:
            labels = labels[:, :labels.shape[1] // 2]
    b, s = tokens.shape
    x = jnp.take(params["embed"].astype(F32), tokens, axis=0)
    body = jax.checkpoint(partial(layer, cfg=cfg, precision=precision))
    for i in range(cfg["num_hidden_layers"]):
        x = body(x, jax.tree_util.tree_map(lambda a: a[i],
                                           params["layers"]))
    x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["head"])
    n_pos = labels.shape[1]
    nb = -(-n_pos // LOSS_BLOCK)
    pad = nb * LOSS_BLOCK - n_pos
    xs = jnp.pad(x[:, :n_pos], ((0, 0), (0, pad), (0, 0)))
    ls = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)

    @jax.checkpoint
    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(xs, i * LOSS_BLOCK, LOSS_BLOCK, 1)
        lb = jax.lax.dynamic_slice_in_dim(ls, i * LOSS_BLOCK, LOSS_BLOCK, 1)
        logits = _mm("bsd,dv->bsv", xb, head, precision)
        picked = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None],
                                     axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(lb >= 0, nll, 0.0))

    return jnp.sum(jax.lax.map(block, jnp.arange(nb))) / (b * n_pos)


def _adamw_step(params, m, v, count, tokens, labels, *, cfg, precision,
                fault):
    tr = cfg["train"]
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels, cfg,
                                              precision, fault)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                      for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, tr["clip_norm"] / (gn + 1e-9))
    grads = jax.tree_util.tree_map(
        lambda g: (g.astype(F32) * scale).astype(g.dtype), grads)
    count = count + 1
    b1, b2 = tr["adam_b1"], tr["adam_b2"]
    bc1 = 1.0 - b1 ** count.astype(F32)
    bc2 = 1.0 - b2 ** count.astype(F32)
    lr, wd, eps = tr["learning_rate"], tr["weight_decay"], tr["adam_eps"]

    def upd(p, g, m_, v_):
        g = g.astype(F32)
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        step = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) \
            + lr * wd * p.astype(F32)
        return (p.astype(F32) - step).astype(p.dtype), m_, v_

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return loss, W.leaf_norms(grads), pick(0), pick(1), pick(2), count


@lru_cache(maxsize=8)
def _step_fn(cfg_json: str, precision: str, fault):
    """The jitted step for a configuration, made once per process."""
    return jax.jit(partial(_adamw_step, cfg=json.loads(cfg_json),
                           precision=precision, fault=fault),
                   donate_argnums=(0, 1, 2))


def train_readings(cfg: dict, seed: int, steps: int, batch: int, seq: int,
                   precision="f32", fault=None, device=None) -> dict:
    """The reference's readings over the first ``steps`` steps from the
    seed's weights and batches: each step's loss, the per-leaf norms of
    the first (clipped) gradient, and the per-leaf norms of the change of
    the weights after ``steps`` steps."""
    step = _step_fn(json.dumps(cfg, sort_keys=True), precision, fault)
    params = W.make(cfg, seed, device)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, F32), p))
    m, v = zeros(params), zeros(params)
    count = jnp.zeros((), jnp.int32)
    losses, g1 = [], None
    for i in range(steps):
        host = W.batch(cfg["vocab_size"], batch, seq, seed, i)
        loss, gnorms, params, m, v, count = step(
            params, m, v, count, host["tokens"], host["labels"])
        losses.append(float(loss))
        if i == 0:
            g1 = [float(x) for x in jax.device_get(gnorms)]
    del m, v
    key = W.seed_key(seed)
    delta = W.delta_norms_fn(cfg)(params, key)
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": [float(x) for x in jax.device_get(delta)]}
