"""Mixture-of-Experts with expert parallelism.

Experts are sharded over the ``model`` mesh axis.  Routing is computed
redundantly on every model-rank for its local batch shard; each rank gathers
only the tokens assigned to ITS experts into fixed-capacity buffers (the
SMAUG command-queue analogue: tiles whose partial results belong to one
expert land on that expert's queue), computes them, and the per-rank partial
outputs are combined with one psum over ``model`` — the same collective cost
as the TP all-reduce it replaces for a dense MLP.  Without such a mesh the
whole expert set runs as the one rank of one.

Dispatch is gather/scatter-index based (no one-hot dispatch einsums), so HLO
FLOPs stay close to the useful expert FLOPs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig, MoEConfig
from repro.dist import context as dist_ctx
from repro.models.layers import Leaf, dense_init


def moe_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    e = cfg.moe
    d, dff = cfg.d_model, e.d_ff_expert
    r = jax.random.split(rng, 5)
    scale = 1.0 / math.sqrt(d)

    def experts(rng_, n, in_d, out_d, axes):
        w = jax.random.normal(rng_, (n, in_d, out_d), jnp.float32) / math.sqrt(in_d)
        return Leaf(w.astype(dtype), axes)

    p = {
        "router": Leaf(jax.random.normal(r[0], (d, e.n_experts), jnp.float32)
                       * scale, ("d_model", None)),
        "gate": experts(r[1], e.n_experts, d, dff, ("experts", "d_model", None)),
        "up": experts(r[2], e.n_experts, d, dff, ("experts", "d_model", None)),
        "down": experts(r[3], e.n_experts, dff, d, ("experts", None, "d_model")),
    }
    if e.n_shared:
        # shared experts: always-on, TP-sharded like a dense MLP
        from repro.models.layers import mlp_init
        p["shared"] = mlp_init(r[4], d, e.n_shared * dff, "swiglu", dtype)
    return p


@jax.named_scope("route")
def _route(x32, router_w, e: MoEConfig):
    """Returns (weights (T,k) f32, experts (T,k) i32, aux dict).  The top-k
    softmax probabilities are renormalised to sum to one, or, where the
    config says not (DeepSeek-V2), scaled by ``routed_scaling_factor``."""
    n_experts = e.n_experts
    logits = x32 @ router_w                                # (T, E) f32
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    elif e.routed_scaling_factor != 1.0:
        w = w * e.routed_scaling_factor
    # load-balance aux loss (Switch-style) + router z-loss
    T = x32.shape[0]
    me = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(idx[:, 0], n_experts, dtype=jnp.float32)
    ce = jnp.mean(onehot, axis=0)
    aux = {
        "load_balance": n_experts * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return w, idx, aux


def _dispatch_indices(e_idx, n_experts, e_start, e_local, capacity):
    """Compute capacity-buffer coordinates for the LOCAL expert shard.

    e_idx: (T, k) global expert assignment.  Returns:
      buf_token (e_local, capacity): token id feeding each buffer slot
        (sentinel T for empty slots),
      slot_of (T, k): flattened local buffer slot per assignment
        (sentinel e_local*capacity for non-local / overflowed).
    """
    T, k = e_idx.shape
    flat = e_idx.reshape(-1)                               # (T*k,) token-major
    onehot = (flat[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                   # position per expert
    pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]  # (T*k,)
    local = (flat >= e_start) & (flat < e_start + e_local) & (pos < capacity)
    e_loc = jnp.where(local, flat - e_start, e_local)      # OOB when not ours
    slot_of = jnp.where(local, e_loc * capacity + pos, e_local * capacity)
    token_of = jnp.arange(T * k) // k
    buf_token = jnp.full((e_local * capacity,), T, dtype=jnp.int32)
    buf_token = buf_token.at[slot_of].set(
        jnp.where(local, token_of, T), mode="drop")
    return buf_token.reshape(e_local, capacity), slot_of.reshape(T, k)


@jax.named_scope("dispatch")
def _dispatch(x, e_idx, n_experts, e_start, e_local, capacity):
    """x: (T, d) -> (capacity buffers (e_local, capacity, d) of the local
    experts' tokens, slot_of (T, k) as ``_dispatch_indices`` gives it)."""
    d = x.shape[1]
    buf_token, slot_of = _dispatch_indices(e_idx, n_experts, e_start, e_local,
                                           capacity)
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xb = xpad[buf_token.reshape(-1)].reshape(e_local, capacity, d)
    return xb, slot_of


@jax.named_scope("combine")
def _combine(yb, w, slot_of, psum_axis, dtype):
    """Each token's expert outputs (yb: (e_local, capacity, d)) gathered
    back from its slots and summed with its routing weights w (T, k)."""
    d = yb.shape[-1]
    ypad = jnp.concatenate([yb.reshape(-1, d), jnp.zeros((1, d), yb.dtype)],
                           axis=0)
    out = jnp.zeros((w.shape[0], d), jnp.float32)
    for j in range(w.shape[1]):
        out = out + w[:, j:j + 1] * ypad[slot_of[:, j]].astype(jnp.float32)
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    return out.astype(dtype)


@jax.named_scope("experts")
def _expert_ffn(p_gate, p_up, p_down, xb, activation="swiglu"):
    """xb: (E_local, C, d) -> (E_local, C, d)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, p_gate))
    u = jnp.einsum("ecd,edf->ecf", xb, p_up)
    return jnp.einsum("ecf,efd->ecd", g * u, p_down)


@jax.named_scope("moe")
def moe_apply(p, x, cfg: ModelConfig):
    """x: (B, S, d) -> (out (B, S, d), aux losses dict).

    Uses shard_map EP over the 'model' axis when a mesh with a non-trivial
    'model' axis is active and divides n_experts; otherwise single-shard.
    """
    B, S, d = x.shape
    e = cfg.moe
    mesh = dist_ctx.get_mesh()
    tp = dist_ctx.mesh_axis_size("model")
    use_ep = (mesh is not None and tp > 1 and e.n_experts % tp == 0)

    if use_ep:
        from jax.sharding import PartitionSpec as P
        dp = dist_ctx.dp_axes()
        xspec = P(dp if dp else None, None, None)
        espec = P(None, "model", None, None)

        def inner(xl, router_w, gate, up, down):
            rank = jax.lax.axis_index("model")
            pl = {"router": router_w, "gate": gate[0], "up": up[0],
                  "down": down[0]}
            # note: inside shard_map the expert leading dim is already local,
            # so treat the shard as the full expert set with offset rank.
            T = xl.shape[0] * xl.shape[1]
            out, aux = _moe_local_shard(pl, xl.reshape(T, d), cfg, rank, tp,
                                        "model")
            lb, rz = aux["load_balance"], aux["router_z"]
            if dp:  # make aux scalars truly replicated across data shards
                lb = jax.lax.pmean(lb, dp)
                rz = jax.lax.pmean(rz, dp)
            return out.reshape(xl.shape), lb, rz

        from repro.core.compat import shard_map
        out, lb, rz = shard_map(
            inner, mesh=mesh,
            in_specs=(xspec, P(None, None), espec, espec, espec),
            out_specs=(xspec, P(), P()),
        )(x, p["router"], p["gate"][None], p["up"][None], p["down"][None])
        aux = {"load_balance": lb, "router_z": rz}
    else:
        out, aux = _moe_local_shard(p, x.reshape(B * S, d), cfg, 0, 1, None)
        out = out.reshape(B, S, d)

    if "shared" in p:
        from repro.models.layers import mlp_apply
        out = out + mlp_apply(p["shared"], x, "swiglu")
    return out, aux


def _moe_local_shard(p, x, cfg, ep_rank, ep_size, psum_axis):
    """The MoE of rank ``ep_rank`` of ``ep_size``, whose expert params hold
    its own ``n_experts / ep_size`` experts (all of them at one rank).
    x: (T, d) local tokens.  Returns (out (T, d), aux): out is this rank's
    share of the sum over the ranks, psummed over ``psum_axis`` if given."""
    e = cfg.moe
    T = x.shape[0]
    e_local = e.n_experts // ep_size
    e_start = ep_rank * e_local
    capacity = max(1, math.ceil(T * e.top_k * e.capacity_factor / e.n_experts))
    w, idx, aux = _route(x.astype(jnp.float32), p["router"], e)
    xb, slot_of = _dispatch(x, idx, e.n_experts, e_start, e_local, capacity)
    yb = _expert_ffn(p["gate"], p["up"], p["down"], xb)
    return _combine(yb, w, slot_of, psum_axis, x.dtype), aux
