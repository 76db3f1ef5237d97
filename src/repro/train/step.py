"""Training step: value_and_grad + AdamW, with optional microbatch gradient
accumulation (hides the DP all-reduce behind compute and divides live
activation memory) and remat already applied inside the model scan.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import transformer as T
from repro.optim import adamw_init, adamw_update, clip_by_global_norm


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    grad_clip: float = 1.0
    weight_decay: float = 0.1
    n_microbatches: int = 1     # >1 => gradient accumulation via scan


def init_train_state(cfg: ModelConfig, rng):
    params, axes = T.init_params(cfg, rng)
    opt = adamw_init(params)
    opt_axes = {"m": axes, "v": axes, "count": ()}
    return params, opt, axes, opt_axes


def make_train_step(cfg: ModelConfig, tc: TrainConfig):
    """Returns train_step(params, opt_state, batch, step) -> (params,
    opt_state, metrics).  Suitable for jax.jit with shardings."""
    from repro.optim.optimizers import cosine_schedule
    lr_fn = cosine_schedule(tc.lr, tc.warmup, tc.total_steps)

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: T.loss_fn(cfg, p, batch), has_aux=True)(params)
        return loss, metrics, grads

    def train_step(params, opt_state, batch, step):
        if tc.n_microbatches > 1:
            n = tc.n_microbatches

            def reshape(x):
                return x.reshape(n, x.shape[0] // n, *x.shape[1:])
            micro = jax.tree_util.tree_map(reshape, batch)

            def acc_body(acc, mb):
                loss, metrics, grads = grads_of(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc,
                                             (loss, grads))
                return acc, metrics
            zero = (jnp.zeros(()),
                    jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (loss_sum, grads), metrics = jax.lax.scan(acc_body, zero, micro)
            grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            loss = loss_sum / n
            metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0),
                                             metrics)
        else:
            loss, metrics, grads = grads_of(params, batch)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
            lr = lr_fn(step)
            params, opt_state = adamw_update(
                grads, opt_state, params, lr=lr,
                weight_decay=tc.weight_decay)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    return train_step
