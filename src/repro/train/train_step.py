"""Training and serving step factories.

``make_train_step`` builds the canonical step:
  loss+grad (remat inside the model) -> optional microbatch
  gradient accumulation (scan over microbatches — activation memory
  scales with microbatch, not global batch; DP all-reduce of microbatch
  k overlaps compute of k+1 under XLA's latency-hiding scheduler) ->
  optional global-norm clipping -> optimizer update with donation.

The xMem estimator consumes the same pieces (fwd_bwd / update / opt_init)
— the estimator *is* wired to the real training step, not a model of it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models import model as M
from .optimizer import Optimizer, clip_by_global_norm, get_optimizer


@dataclasses.dataclass(frozen=True)
class TrainPolicy:
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    clip_norm: float | None = 1.0
    microbatches: int = 1          # gradient-accumulation steps
    opt_kwargs: tuple = ()


def make_fwd_bwd(cfg: ModelConfig, microbatches: int = 1) -> Callable:
    """(params, batch) -> (loss, grads), optionally with gradient
    accumulation over ``microbatches`` — the same scan the real train
    step runs, so the estimator sees accumulation's memory profile
    (activations scale with the microbatch, f32 accumulators persist
    across the scan). ``batch`` leading dims must divide evenly."""
    def fwd_bwd(params, batch):
        return jax.value_and_grad(M.loss_fn)(params, batch, cfg)
    if microbatches <= 1:
        return fwd_bwd
    n = microbatches

    def fwd_bwd_accum(params, batch):
        mb = _split_microbatches(batch, n)

        def acc_body(carry, micro):
            loss_sum, g_acc = carry
            loss, grads = fwd_bwd(params, micro)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), g_acc, grads)
            return (loss_sum + loss, g_acc), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, g_acc), _ = jax.lax.scan(acc_body, (0.0, g0), mb)
        grads = jax.tree_util.tree_map(lambda g: g / n, g_acc)
        return loss_sum / n, grads

    return fwd_bwd_accum


def _split_microbatches(batch: dict, n: int) -> dict:
    def split(x):
        b = x.shape[0]
        assert b % n == 0, f"batch {b} not divisible by {n} microbatches"
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def make_train_step(cfg: ModelConfig, policy: TrainPolicy
                    ) -> tuple[Callable, Optimizer]:
    """Returns (train_step(params, opt_state, batch) -> (loss, params,
    opt_state), optimizer). Donation is applied at jit time by the
    launcher (donate_argnums=(0, 1))."""
    opt = get_optimizer(policy.optimizer, lr=policy.learning_rate,
                        **dict(policy.opt_kwargs))
    update_fn = opt.update
    if policy.clip_norm is not None:
        update_fn = clip_by_global_norm(update_fn, policy.clip_norm)
    # the accumulation scan lives in make_fwd_bwd so the estimator hooks
    # and the real step share it by construction (identical code paths)
    fwd_bwd = make_fwd_bwd(cfg, policy.microbatches)

    def train_step(params, opt_state, batch):
        loss, grads = fwd_bwd(params, batch)
        # a named scope labels the update's operations in the compiled
        # program's metadata (see models/model.py); it adds no operation
        with jax.named_scope("optimizer"):
            new_params, new_state = update_fn(params, grads, opt_state)
        return loss, new_params, new_state

    return train_step, opt


# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig) -> Callable:
    """Forward over the full prompt -> last-position logits."""
    def prefill_step(params, batch):
        x = M.embed_inputs(params, batch, cfg)
        h = M.backbone(params, x, cfg,
                       positions=jnp.arange(x.shape[1]))
        return M.logits_fn(params, h[:, -1:], cfg)
    return prefill_step


def make_serve_step(cfg: ModelConfig, cache_len: int) -> Callable:
    """One-token decode against a cache of ``cache_len`` context."""
    def serve_step(params, cache, batch):
        logits, new_cache = M.decode_step(
            params, cache, batch, jnp.int32(cache_len), cfg)
        return logits, new_cache
    return serve_step


# ---------------------------------------------------------------------------
def make_estimator_hooks(cfg: ModelConfig, policy: TrainPolicy):
    """The (fwd_bwd, update, opt_init) triple xMem estimates from —
    identical code paths to the real step (first-class integration).
    ``policy.microbatches`` is honored: the estimator's forward phase
    runs the same accumulation scan the real step would, so replanning
    a rejected job onto more microbatches actually changes (shrinks)
    the estimate."""
    opt = get_optimizer(policy.optimizer, lr=policy.learning_rate,
                        **dict(policy.opt_kwargs))
    update_fn = opt.update
    if policy.clip_norm is not None:
        update_fn = clip_by_global_norm(update_fn, policy.clip_norm)
    return (make_fwd_bwd(cfg, policy.microbatches), update_fn, opt.init)
