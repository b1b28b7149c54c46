"""The plain reference of ``dense_lm``, laid out over a mesh.

The same float32, ``Precision.HIGHEST`` mathematics as ``dense_lm`` (its
loss, clipped gradients and AdamW, its weights made from the seed), run
on the devices of a mesh: the weights and Adam's moments are split over
the mesh's ``model`` axis by a plain table of this module's own, and
GSPMD partitions the step. The table (:data:`LAYOUT`): ``wq``, ``wk``,
``wv``, ``w_gate`` and ``w_up`` along their output axis, ``wo`` and
``w_down`` along their input axis, the embedding and the head along the
vocabulary; the norms are replicated, and so is the batch. It imports
nothing of the program.

``fault="no_exchange"``: the first layer's attention output projection,
which is row-parallel, is not summed over the model axis: its output is
the first shard's partial product alone, as on a device whose
all-reduce of that projection was left out. Its backward pass is that
of the partial product it computed, with the gradient of every row of
``wo`` kept. ``precision="fp8"`` and ``fault="half_batch"`` are
``dense_lm``'s. The fault is an argument of one compiled step, so the
sound step and its faults share one compilation.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import weights as W
from . import dense_lm

F32 = jnp.float32
MODEL = "model"
FAULTS = {None: 0, "half_batch": 1, "no_exchange": 2}
# leaf -> the axis, counted from the end, split over the model axis
LAYOUT = {
    "embed": -2, "head": -1,
    "layers/attn/wq": -1, "layers/attn/wk": -1, "layers/attn/wv": -1,
    "layers/attn/wo": -2,
    "layers/mlp/w_gate": -1, "layers/mlp/w_up": -1,
    "layers/mlp/w_down": -2,
}


def shardings(cfg: dict, mesh) -> dict:
    """The weights' shardings on ``mesh``, as :data:`LAYOUT` splits
    them, in the program's parameter tree."""
    flat = {}
    for path, (shape, _) in W.leaf_specs(cfg).items():
        spec = [None] * len(shape)
        if path in LAYOUT:
            spec[LAYOUT[path]] = MODEL
        flat[path] = NamedSharding(mesh, P(*spec))
    return W.nest(flat)


def exchange_left_out(params: dict, shards: int, on=True) -> dict:
    """``params`` with the first layer's ``wo`` counting, in the forward
    pass, only the rows of the first of ``shards`` shards, where ``on``
    (a traced boolean) holds; its gradient passes to every row."""
    wo = params["layers"]["attn"]["wo"]              # [L, h * hd, d]
    n, e, _ = wo.shape
    keep = ((jnp.arange(n) > 0)[:, None, None]
            | (jnp.arange(e) < e // shards)[None, :, None]
            | jnp.logical_not(on))
    wo = wo + jax.lax.stop_gradient(jnp.where(keep, wo, 0) - wo)
    attn = {**params["layers"]["attn"], "wo": wo}
    return {**params, "layers": {**params["layers"], "attn": attn}}


def loss_fn(params, tokens, labels, fault, cfg, precision="f32",
            shards=1):
    """``dense_lm.loss_fn`` with the fault a traced code
    (:data:`FAULTS`), so that the sound step and its faults are one
    program: ``half_batch`` masks the labels it leaves out, and the
    layers are taken by a ``lax.scan`` over their stacked weights (one
    layer's program, where ``dense_lm`` unrolls them)."""
    params = exchange_left_out(params, shards,
                               fault == FAULTS["no_exchange"])
    b, s = labels.shape
    half = fault == FAULTS["half_batch"]
    rows = jnp.where(half & (b >= 2), b // 2, b)
    cols = jnp.where(half & (b < 2), s // 2, s)
    kept = ((jnp.arange(b) < rows)[:, None]
            & (jnp.arange(s) < cols)[None, :])
    labels = jnp.where(kept, labels, -1)
    x = jnp.take(params["embed"].astype(F32), tokens, axis=0)
    body = jax.checkpoint(partial(dense_lm.layer, cfg=cfg,
                                  precision=precision))
    x, _ = jax.lax.scan(lambda x, lw: (body(x, lw), None), x,
                        params["layers"])
    x = dense_lm.rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["head"])
    blk = dense_lm.LOSS_BLOCK
    nb = -(-s // blk)
    pad = nb * blk - s
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    ls = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)

    @jax.checkpoint
    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(xs, i * blk, blk, 1)
        lb = jax.lax.dynamic_slice_in_dim(ls, i * blk, blk, 1)
        logits = dense_lm._mm("bsd,dv->bsv", xb, head, precision)
        picked = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None],
                                     axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(lb >= 0, nll, 0.0))

    return jnp.sum(jax.lax.map(block, jnp.arange(nb))) / (rows * cols)


def _adamw_step(params, m, v, count, tokens, labels, fault, *, cfg,
                precision, shards):
    """``dense_lm``'s step (clip to a global norm, then AdamW) on
    :func:`loss_fn`."""
    tr = cfg["train"]
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels, fault,
                                              cfg, precision, shards)
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
def _step_fn(cfg_json: str, precision: str, mesh):
    """The jitted step for a configuration on ``mesh``, made once per
    process; the fault is an argument, so that one program serves the
    sound step and its faults."""
    cfg = json.loads(cfg_json)
    ps, rep = shardings(cfg, mesh), NamedSharding(mesh, P())
    return jax.jit(partial(_adamw_step, cfg=cfg, precision=precision,
                           shards=mesh.shape[MODEL]),
                   in_shardings=(ps, ps, ps, rep, rep, rep, rep),
                   out_shardings=(rep, rep, ps, ps, ps, rep),
                   donate_argnums=(0, 1, 2))


def delta_norms(cfg: dict, params, key) -> list:
    """Per-leaf f32 norms of ``params`` less the weights of ``key``, in
    ``weights.leaf_specs`` order; the initial weights are made anew in
    ``params``' own shardings, so that no device holds a whole leaf."""
    sh = jax.tree_util.tree_map(lambda x: x.sharding, params)

    def fn(params, key):
        p0 = W.flatten(jax.lax.with_sharding_constraint(W._make(cfg, key),
                                                        sh))
        p = W.flatten(params)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            p[k].astype(F32) - p0[k].astype(F32)))) for k in p0])
    return [float(x) for x in jax.device_get(jax.jit(fn)(params, key))]


def train(cfg: dict, params, batches, mesh, precision="f32", fault=None):
    """Steps from ``params`` (placed in :func:`shardings`) over
    ``batches`` (host ``tokens`` and ``labels``): ``(losses, the per-leaf
    norms of the first clipped gradient, the weights after the last
    step)``."""
    step = _step_fn(json.dumps(cfg, sort_keys=True), precision, mesh)
    code = jnp.int32(FAULTS[fault])
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, F32), p),
        out_shardings=shardings(cfg, mesh))
    m, v = zeros(params), zeros(params)
    count = jnp.zeros((), jnp.int32)
    losses, g1 = [], None
    for b in batches:
        loss, gnorms, params, m, v, count = step(
            params, m, v, count, b["tokens"], b["labels"], code)
        losses.append(float(loss))
        if g1 is None:
            g1 = [float(x) for x in jax.device_get(gnorms)]
    return losses, g1, params


def train_readings(cfg: dict, seed: int, steps: int, batch: int, seq: int,
                   mesh, precision="f32", fault=None) -> dict:
    """``dense_lm.train_readings`` on ``mesh``: each step's loss, the
    per-leaf norms of the first (clipped) gradient, and the per-leaf
    norms of the change of the weights after ``steps`` steps."""
    key = W.seed_key(seed)
    params = jax.jit(partial(W._make, cfg),
                     out_shardings=shardings(cfg, mesh))(key)
    losses, g1, params = train(
        cfg, params, [W.batch(cfg["vocab_size"], batch, seq, seed, i)
                      for i in range(steps)], mesh, precision, fault)
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": delta_norms(cfg, params, key)}
