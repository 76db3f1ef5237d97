"""Common layers: norms, embeddings, RoPE, MLPs.

Parameters are plain pytrees of jnp arrays.  Each init helper returns
``Leaf(value, axes)`` pairs where ``axes`` is a tuple of *logical* axis names
used by the sharding rule engine (repro.dist.sharding).  ``split_leaves``
separates a Leaf-tree into (params, axes) trees.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Leaf:
    value: Any
    axes: Tuple[Optional[str], ...]


def _is_leaf(x):
    return isinstance(x, Leaf)


def split_leaves(tree):
    params = jax.tree_util.tree_map(lambda l: l.value, tree, is_leaf=_is_leaf)
    axes = jax.tree_util.tree_map(lambda l: l.axes, tree, is_leaf=_is_leaf)
    return params, axes


def dense_init(rng, in_dim, out_dim, axes, dtype=jnp.bfloat16, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    w = jax.random.normal(rng, (in_dim, out_dim), dtype=jnp.float32) * scale
    return Leaf(w.astype(dtype), axes)


def embed_init(rng, vocab, d_model, dtype=jnp.bfloat16):
    w = jax.random.normal(rng, (vocab, d_model), dtype=jnp.float32) * 0.02
    return Leaf(w.astype(dtype), ("vocab", "d_model"))


def norm_init(d_model):
    # norm scales stay fp32 and replicated
    return Leaf(jnp.ones((d_model,), dtype=jnp.float32), (None,))


def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * scale
    return out.astype(x.dtype)


def layernorm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps) * scale
    return out.astype(x.dtype)


def apply_norm(kind, x, scale):
    return rmsnorm(x, scale) if kind == "rmsnorm" else layernorm(x, scale)


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature term (DeepSeek-V2's yarn_get_mscale)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, yarn):
    """YaRN's rotary frequencies (DeepseekV2YarnRotaryEmbedding): the
    high-frequency pairs keep theta's, the low-frequency ones are divided
    by ``factor``, with a linear ramp between the correction dims."""
    def corr_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    keep = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return base / yarn.factor * (1.0 - keep) + base * keep


def rope_tables(positions, dim, theta, yarn=None):
    """positions: (...,) int32 -> cos/sin of shape positions.shape + (dim/2,).
    ``yarn``: a ``YaRNConfig`` for YaRN's frequencies and table scale."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    else:
        inv = jnp.asarray(yarn_inv_freq(dim, theta, yarn), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        scale = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if scale != 1.0:
            cos, sin = cos * scale, sin * scale
    return cos, sin


def apply_rope(x, cos, sin):
    """x: (..., S, D); cos/sin: broadcastable (..., S, D/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(n_ctx, d_model):
    pos = np.arange(n_ctx)[:, None]
    dim = np.arange(0, d_model, 2)[None, :] / d_model
    ang = pos / (10_000.0 ** dim)
    table = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(table, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# MLP


def mlp_init(rng, d_model, d_ff, activation, dtype=jnp.bfloat16):
    r1, r2, r3 = jax.random.split(rng, 3)
    gated = activation in ("swiglu", "geglu")
    p = {
        "up": dense_init(r1, d_model, d_ff, ("d_model", "d_ff"), dtype),
        "down": dense_init(r2, d_ff, d_model, ("d_ff", "d_model"), dtype),
    }
    if gated:
        p["gate"] = dense_init(r3, d_model, d_ff, ("d_model", "d_ff"), dtype)
    return p


def _act(name, x):
    if name in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if name == "geglu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.gelu(x, approximate=True)  # plain gelu


@jax.named_scope("mlp")
def mlp_apply(p, x, activation):
    from repro.dist.tp import tp_project
    up = x @ p["up"]
    if "gate" in p:
        up = _act(activation, x @ p["gate"]) * up
    else:
        up = _act(activation, up)
    return tp_project(up, p["down"])
