"""Tensor-parallel helpers.

``tp_project`` closes a TP region: the activation is sharded on its
contraction dimension (d_ff / heads_x_dim) over the 'model' axis, the down
projection produces partial sums, and the partials are reduced.  Under jit +
GSPMD the all-reduce is inserted by the partitioner, so the helper is just
the matmul; under an explicit shard_map (the 'model' axis is bound) it must
psum itself.
"""
from __future__ import annotations

import jax

from repro.dist import context as dist_ctx


def _axis_bound(name: str) -> bool:
    """True when ``name`` is a bound collective axis (inside shard_map)."""
    try:
        jax.lax.axis_index(name)
        return True
    except (NameError, KeyError):
        return False


def tp_project(x, w, axis_name: str = "model"):
    """x @ w, reduced over ``axis_name`` when that axis is explicitly bound."""
    out = x @ w
    if dist_ctx.mesh_axis_size(axis_name) > 1 and _axis_bound(axis_name):
        out = jax.lax.psum(out, axis_name)
    return out
