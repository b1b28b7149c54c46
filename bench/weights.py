"""Weights and batches of a dense decoder, made from the seed.

The benchmark, not the program, makes the weights: one jitted call on
the device, in the type they are trained in, laid out as the program's
parameter tree (``embed``, ``head`` unless tied, ``final_norm``, and the
layers stacked on a leading axis). The reference makes the same weights
by the same call, so it takes nothing the program has made. Each leaf
is drawn from its own key, ``fold_in(key(seed), leaf index)``, with the
program's own scales (0.02 for the embedding and head, 1/sqrt(fan-in)
for the other matrices, ones for the norms).
"""
from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number up to 62 bits."""
    import jax
    if not 0 <= seed < 2**62:
        raise ValueError(f"seed {seed} out of range [0, 2**62)")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaf_specs(cfg: dict) -> dict:
    """``{path: (shape, scale)}``; scale ``None`` means a norm of ones.
    Paths name the leaves of the program's parameter tree."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    specs = {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), None),
        "layers/ln1": ((n, d), None),
        "layers/ln2": ((n, d), None),
        "layers/attn/wq": ((n, d, h * hd), 1 / math.sqrt(d)),
        "layers/attn/wk": ((n, d, kv * hd), 1 / math.sqrt(d)),
        "layers/attn/wv": ((n, d, kv * hd), 1 / math.sqrt(d)),
        "layers/attn/wo": ((n, h * hd, d), 1 / math.sqrt(d)),
        "layers/mlp/w_gate": ((n, d, f), 1 / math.sqrt(d)),
        "layers/mlp/w_up": ((n, d, f), 1 / math.sqrt(d)),
        "layers/mlp/w_down": ((n, f, d), 1 / math.sqrt(f)),
    }
    if not cfg["tie_word_embeddings"]:
        specs["head"] = ((d, v), 0.02)
    return dict(sorted(specs.items()))


def nest(flat: dict) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``."""
    out: dict = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """Inverse of :func:`nest`, with paths in sorted order."""
    out = {}
    for k in sorted(tree):
        path = f"{prefix}{k}"
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], path + "/"))
        else:
            out[path] = tree[k]
    return out


def _make(cfg: dict, key):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["param_dtype"])
    flat = {}
    for i, (path, (shape, scale)) in enumerate(leaf_specs(cfg).items()):
        if scale is None:
            flat[path] = jnp.ones(shape, dtype)
        else:
            flat[path] = (jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
                          * scale).astype(dtype)
    return nest(flat)


def make(cfg: dict, seed: int, device=None):
    """The weights for ``seed``, made on ``device`` in one jitted call."""
    import jax
    fn = jax.jit(partial(_make, cfg))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)


def delta_norms_fn(cfg: dict):
    """Jitted ``(params, key) -> [per-leaf ||params - weights(key)||]``
    in f32, in :func:`leaf_specs` order: the change since
    initialization, with the initial weights made anew on the device.
    Made once per configuration and process."""
    return _delta_norms_fn(json.dumps(cfg, sort_keys=True))


@lru_cache(maxsize=8)
def _delta_norms_fn(cfg_json: str):
    import jax
    import jax.numpy as jnp
    cfg = json.loads(cfg_json)

    def fn(params, key):
        p0 = flatten(_make(cfg, key))
        p = flatten(params)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            p[k].astype(jnp.float32) - p0[k].astype(jnp.float32))))
            for k in p0])
    return jax.jit(fn)


def leaf_norms(tree) -> "jax.Array":
    """Per-leaf f32 norms of a tree, in :func:`flatten` order."""
    import jax.numpy as jnp
    flat = flatten(tree)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        flat[k].astype(jnp.float32)))) for k in flat])


def batch(vocab: int, batch_size: int, seq: int, seed: int, step: int
          ) -> dict:
    """Step ``step``'s batch: token ids uniform over ``[0, vocab)``,
    a pure function of ``(seed, step)``; labels are the next ids."""
    rng = np.random.default_rng([seed, step])
    ids = rng.integers(0, vocab, size=(batch_size, seq + 1), dtype=np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
