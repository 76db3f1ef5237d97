"""Plain float32 reference of DeepSeek-V2's forward pass, for the tests
that hold ``repro.models.transformer`` to it.

Written from the paper (arXiv:2405.04434) and the published
``modeling_deepseek.py`` in straightforward ``jax.numpy``: every weight is
widened to float32 and every product runs under
``jax.default_matmul_precision("highest")``.  No cache, no kernels, no
capacity and no sharding: multi-head latent attention in its naive,
expanded form (the latent ``c_kv`` is projected up to per-head keys and
values and attention is a plain causal softmax), every routed expert
computed for every token and masked by its routing weight, and the whole
sequence at once.  It reads the program's parameter tree and its
``ModelConfig`` and nothing else of the program.

As published:
  * RMSNorm (epsilon 1e-6) before attention, before the FFN, on the
    latent ``c_kv`` and before the head; the head is untied;
  * the leading ``n_dense_layers`` layers run a dense SwiGLU MLP of width
    ``d_ff``, the others the MoE: softmax over all routed experts, the
    top-k probabilities used as they are (``norm_topk_prob`` false) times
    ``routed_scaling_factor``, or renormalised where the config says so,
    plus the shared experts, one SwiGLU of width ``n_shared * d_ff_expert``;
  * YaRN rope on the ``qk_rope_dim`` dims (DeepseekV2YarnRotaryEmbedding)
    and the softmax scale ``(qk_nope + qk_rope) ** -0.5`` times
    ``yarn_get_mscale(factor, mscale_all_dim) ** 2``.

Departures:
  * Rope pairs: the published ``apply_rotary_pos_emb`` first de-interleaves
    each head's rope dims (pairs (0, 1), (2, 3), ...) and then rotates
    halves; the program and this reference rotate halves of the dims as
    they are stored.  With random weights that is a fixed permutation of
    the columns of the rope part of ``q`` and ``kv_a``, so the two
    conventions compute the same function of differently laid-out weights.
  * Weights are the program's bf16 values widened to float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EPS = 1e-6


def rmsnorm(x, gain):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * gain


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(dim, theta, yarn):
    """The rotary frequencies of ``dim`` dims: theta's, or YaRN's blend of
    theta's and theta's over ``factor`` with a linear ramp between the
    correction dims of ``beta_fast`` and ``beta_slow`` rotations."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    if yarn is None:
        return extra

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / yarn.factor * ramp + extra * (1 - ramp)


def rope(x, inv_freq, mscale):
    """x (S, ..., dr): rotate halves by position."""
    S, dr = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (dr // 2,))
    c, s = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(cfg, w, x):
    """One sequence, naive MLA: x (S, d) -> (S, d)."""
    m, H, S = cfg.mla, cfg.n_heads, x.shape[0]
    dn, dr, dv, R = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim, m.kv_lora_rank
    y = cfg.yarn
    inv = rope_inv_freq(dr, cfg.rope_theta, y)
    scale = (dn + dr) ** -0.5
    table_mscale = 1.0
    if y is not None:
        table_mscale = (yarn_get_mscale(y.factor, y.mscale)
                        / yarn_get_mscale(y.factor, y.mscale_all_dim))
        if y.mscale_all_dim:
            scale *= yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
    q = (x @ w["q"]).reshape(S, H, dn + dr)
    kv = x @ w["kv_a"]
    c = rmsnorm(kv[:, :R], w["kv_norm"])
    k_pe = rope(kv[:, R:], inv, table_mscale)                  # (S, dr)
    kvb = (c @ w["kv_b"]).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv, table_mscale)],
                        -1)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (S, H, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., dn:])
    return o.reshape(S, H * dv) @ w["o"]


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def route(cfg, w, x):
    """x (T, d) -> each token's weight on each routed expert (T, E): the
    top-k of the softmax over all experts, renormalised or scaled as the
    config says, and 0 elsewhere."""
    e = cfg.moe
    probs = jax.nn.softmax(x @ w["router"], -1)
    top, idx = jax.lax.top_k(probs, e.top_k)
    if e.norm_topk_prob:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * e.routed_scaling_factor
    return jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None],
                                    idx].set(top)


def moe(cfg, w, x):
    """x (T, d): every routed expert on every token, masked by its
    routing weight, plus the shared experts."""
    e = cfg.moe
    gate = route(cfg, w, x)
    out = swiglu(w["shared"], x)
    for j in range(e.n_experts):
        expert = {n: w[n][j] for n in ("gate", "up", "down")}
        out = out + gate[:, j:j + 1] * swiglu(expert, x)
    return out


def layer(cfg, w, h):
    """One decoder layer over h (B, S, d)."""
    B, S, d = h.shape
    a = jax.vmap(lambda x: attention(cfg, w["attn"], x))(
        rmsnorm(h, w["norm1"]))
    h = h + a
    x = rmsnorm(h, w["norm2"]).reshape(B * S, d)
    y = moe(cfg, w["moe"], x) if "moe" in w else swiglu(w["mlp"], x)
    return h + y.reshape(B, S, d)


def forward(cfg, params, tokens):
    """Logits (B, S, V) in float32 of every position of ``tokens`` (B, S),
    from the program's parameter tree."""
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
    stacks = [p["layers"]]
    if cfg.n_dense_layers:
        stacks.insert(0, p["dense_layers"])
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        for stack in stacks:
            n = jax.tree_util.tree_leaves(stack)[0].shape[0]
            for i in range(n):
                h = layer(cfg, jax.tree_util.tree_map(lambda a: a[i], stack),
                          h)
        return rmsnorm(h, p["final_norm"]) @ p["lm_head"]
