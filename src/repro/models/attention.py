"""Attention: GQA/MQA, MLA (DeepSeek), sliding-window, chunked online-softmax.

The chunked (flash-style) path is the default jnp implementation so that 32k+
prefill lowers with O(seq * chunk) live memory; the Pallas kernel in
repro.kernels.flash_attention implements the same dataflow for TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.config import MLAConfig, ModelConfig
from repro.models.layers import (Leaf, dense_init, norm_init, rmsnorm,
                                 yarn_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter init


def attn_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    d, H, Hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        r = jax.random.split(rng, 4)
        return {
            "q": dense_init(r[0], d, H * (m.qk_nope_dim + m.qk_rope_dim),
                            ("d_model", "heads_x_dim")),
            "kv_a": dense_init(r[1], d, m.kv_lora_rank + m.qk_rope_dim,
                               ("d_model", None)),
            "kv_norm": norm_init(m.kv_lora_rank),
            "kv_b": dense_init(r[2], m.kv_lora_rank,
                               H * (m.qk_nope_dim + m.v_head_dim),
                               (None, "heads_x_dim")),
            "o": dense_init(r[3], H * m.v_head_dim, d,
                            ("heads_x_dim", "d_model")),
        }
    r = jax.random.split(rng, 4)
    return {
        "q": dense_init(r[0], d, H * hd, ("d_model", "heads_x_dim")),
        "k": dense_init(r[1], d, Hkv * hd, ("d_model", "kv_heads_x_dim")),
        "v": dense_init(r[2], d, Hkv * hd, ("d_model", "kv_heads_x_dim")),
        "o": dense_init(r[3], H * hd, d, ("heads_x_dim", "d_model")),
    }


# ---------------------------------------------------------------------------
# chunked online-softmax attention (prefill / train)


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      kv_valid=None, chunk=512, scale=None):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D).  Returns (B, H, Sq, D).

    Scans over KV chunks with an online-softmax carry so live memory is
    O(Sq * chunk) rather than O(Sq * Skv).  ``scale`` defaults to D ** -0.5.
    """
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    chunk = min(chunk, Skv)
    if Skv % chunk:  # pad KV to a chunk multiple; padded keys are masked out
        pad = chunk - Skv % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if kv_valid is None:
            kv_valid = Skv
        Skv = Skv + pad
    n_chunks = Skv // chunk

    # NOTE: q stays (B, H, Sq, D) so TP head-sharding is preserved even when
    # Hkv < tp; KV chunks are broadcast to full heads INSIDE the body (free —
    # fused into the einsum).  A (B, Hkv, G, ...) reshape here would force
    # XLA to replicate q across the model axis (observed: +2.1 GB/device of
    # fp32 traffic per layer on tinyllama train_4k).
    q_pos = q_offset + jnp.arange(Sq)
    kc = k.reshape(B, Hkv, n_chunks, chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, n_chunks, chunk, D).transpose(2, 0, 1, 3, 4)
    idx = jnp.arange(n_chunks)
    qf = q.astype(jnp.float32)

    def expand(t):  # (B, Hkv, c, D) -> (B, H, c, D), fusable broadcast
        if G == 1:
            return t
        return jnp.broadcast_to(
            t[:, :, None], (B, Hkv, G, chunk, D)).reshape(B, H, chunk, D)

    def body(carry, xs):
        m, l, acc = carry
        i, k_i, v_i = xs
        k_pos = i * chunk + jnp.arange(chunk)
        s = jnp.einsum("bhqd,bhcd->bhqc", qf,
                       expand(k_i).astype(jnp.float32)) * scale
        mask = jnp.ones((Sq, chunk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None and not (isinstance(window, int) and window == 0):
            # trace-safe: window may be a scalar array; 0 means unlimited
            w_eff = jnp.where(window > 0, window, Sq + Skv + 1)
            mask &= (q_pos[:, None] - k_pos[None, :]) < w_eff
        if kv_valid is not None:
            mask &= (k_pos < kv_valid)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqc,bhcd->bhqd", p, expand(v_i).astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, Sq), dtype=jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (idx, kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, pos, window=0, new=None):
    """Single-token decode.  q: (B, H, 1, D); caches: (B, Hkv, S, D).

    ``pos`` is the current (scalar) position; keys at index > pos are masked.
    ``new``: the (k, v) of position ``pos``, each (B, Hkv, 1, D), attended
    to as the key at ``pos``; the cache's entries from ``pos`` on are then
    masked, so it need not hold them yet.

    The G = H / Hkv query heads of a group meet their K/V head together, so
    no cache entry is repeated per query head.  Scores, softmax and the
    weighted sum are float32, the products exact (``HIGHEST``): the bf16
    cache is upcast, the query and the weights are not rounded down.
    """
    B, H, _, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    scale = D ** -0.5
    f32, exact = jnp.float32, jax.lax.Precision.HIGHEST

    def dot(spec, a, b):
        return jnp.einsum(spec, a, b.astype(f32), precision=exact)

    qg = q.reshape(B, Hkv, H // Hkv, D).astype(f32)
    s = dot("bhgd,bhsd->bhgs", qg, k_cache) * scale
    k_pos = jnp.arange(S)
    mask = k_pos < pos if new is not None else k_pos <= pos
    if window is not None and not (isinstance(window, int) and window == 0):
        w_eff = jnp.where(window > 0, window, S + 1)
        mask &= (pos - k_pos) < w_eff
    s = jnp.where(mask, s, NEG_INF)
    if new is not None:
        s = jnp.concatenate([s, dot("bhgd,bhsd->bhgs", qg, new[0]) * scale],
                            -1)
    w = jax.nn.softmax(s, axis=-1)
    out = dot("bhgs,bhsd->bhgd", w[..., :S], v_cache)
    if new is not None:
        out += dot("bhgs,bhsd->bhgd", w[..., S:], new[1])
    return out.reshape(B, H, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# standard (GQA) attention layer forward


@jax.named_scope("attention")
def gqa_forward(p, x, cos, sin, *, cfg: ModelConfig, causal=True, window=0,
                q_offset=0, xa=None):
    """Full-sequence attention (train/prefill).  Returns (out, (k, v)).

    ``xa``: encoder output for cross attention (k/v from xa, no causal mask).
    """
    from repro.dist.tp import tp_project
    B, S, d = x.shape
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    kv_src = xa if xa is not None else x
    Skv = kv_src.shape[1]
    q = (x @ p["q"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    k = (kv_src @ p["k"]).reshape(B, Skv, Hkv, hd).transpose(0, 2, 1, 3)
    v = (kv_src @ p["v"]).reshape(B, Skv, Hkv, hd).transpose(0, 2, 1, 3)
    if cos is not None and xa is None:
        q = _rope_heads(q, cos, sin)
        k = _rope_heads(k, cos, sin)
    out = chunked_attention(q, k, v, causal=causal and xa is None,
                            window=window, q_offset=q_offset)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return tp_project(out, p["o"]), (k, v)


@jax.named_scope("attention")
def cross_decode(p, x, xk, xv, *, cfg: ModelConfig):
    """One token's cross-attention over the encoder's K/V, each
    (B, Hkv, n_ctx, hd), every key visible.  x: (B, 1, d)."""
    B = x.shape[0]
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ p["q"]).reshape(B, 1, H, hd).transpose(0, 2, 1, 3)
    out = decode_attention(q, xk, xv, pos=xk.shape[2] - 1)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, H * hd) @ p["o"]


@jax.named_scope("attention")
def gqa_decode_step(p, x, cache_k, cache_v, cos, sin, *, cfg: ModelConfig,
                    pos, window=0):
    """One-token decode that leaves the cache as it is.  x: (B, 1, d);
    cache_[kv]: (B, Hkv, S, hd), filled below ``pos``.  The new position's
    key and value are attended to as the key at ``pos`` and returned, for
    the layer scan to write every layer's at once after it ends.

    Returns (out, k_new, v_new), the last two (B, Hkv, 1, hd) in the
    cache's dtype."""
    B = x.shape[0]
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    q = (x @ p["q"]).reshape(B, 1, H, hd).transpose(0, 2, 1, 3)
    k_new = (x @ p["k"]).reshape(B, 1, Hkv, hd).transpose(0, 2, 1, 3)
    v_new = (x @ p["v"]).reshape(B, 1, Hkv, hd).transpose(0, 2, 1, 3)
    if cos is not None:
        q = _rope_heads(q, cos, sin)
        k_new = _rope_heads(k_new, cos, sin)
    k_new, v_new = k_new.astype(cache_k.dtype), v_new.astype(cache_v.dtype)
    out = decode_attention(q, cache_k, cache_v, pos=pos, window=window,
                           new=(k_new, v_new))
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, H * hd)
    return out @ p["o"], k_new, v_new


def _rope_heads(x, cos, sin):
    """x: (B, H, S, D); cos/sin: (S, D/2) or (1, D/2) for decode."""
    from repro.models.layers import apply_rope
    return apply_rope(x, cos[None, None], sin[None, None])


# ---------------------------------------------------------------------------
# MLA (DeepSeek V2) — compressed KV cache


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope) ** -0.5, times YaRN's mscale squared where the
    config scales its rope (DeepseekV2Attention.softmax_scale)."""
    m = cfg.mla
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


@jax.named_scope("attention")
def mla_forward(p, x, cos, sin, *, cfg: ModelConfig, q_offset=0):
    """Train/prefill MLA, naive (expanded) form.  Returns (out, (c_kv, k_rope))."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    q = (x @ p["q"]).reshape(B, S, H, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = x @ p["kv_a"]
    c_kv = rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"])
    k_rope = kv[..., m.kv_lora_rank:]                      # (B, S, dr) shared
    q_rope = _rope_heads(q_rope, cos, sin)
    k_rope = _rope_heads(k_rope[:, None], cos, sin)[:, 0]  # rope on shared key
    # expand compressed kv
    kvb = (c_kv @ p["kv_b"]).reshape(B, S, H, dn + dv).transpose(0, 2, 1, 3)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (B, H, S, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    # pad v head dim to qk dim for the shared kernel, then slice back
    out = chunked_attention(qf, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                               (0, dn + dr - dv))),
                            causal=True, q_offset=q_offset,
                            scale=mla_softmax_scale(cfg))[..., :dv]
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    return out @ p["o"], (c_kv, k_rope)


@jax.named_scope("attention")
def mla_decode(p, x, cache_ckv, cache_krope, cos, sin, *, cfg: ModelConfig, pos):
    """Absorbed-matmul MLA decode: attention runs in the compressed space.
    cache_ckv: (B, S, lora); cache_krope: (B, S, dr)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv, R = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim, m.kv_lora_rank
    scale = mla_softmax_scale(cfg)
    q = (x @ p["q"]).reshape(B, 1, H, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], _rope_heads(q[..., dn:], cos, sin)
    kv = x @ p["kv_a"]
    c_new = rmsnorm(kv[..., :R], p["kv_norm"])             # (B, 1, R)
    kr_new = _rope_heads(kv[:, None, :, R:], cos, sin)[:, 0]
    with jax.named_scope("kv_cache"):
        cache_ckv = jax.lax.dynamic_update_slice(
            cache_ckv, c_new.astype(cache_ckv.dtype), (0, pos, 0))
        cache_krope = jax.lax.dynamic_update_slice(
            cache_krope, kr_new.astype(cache_krope.dtype), (0, pos, 0))
    wkb = p["kv_b"].reshape(R, H, dn + dv)
    w_k, w_v = wkb[..., :dn], wkb[..., dn:]
    # absorb: q into compressed space
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope[:, :, 0].astype(jnp.float32),
                     w_k.astype(jnp.float32))
    s = (jnp.einsum("bhr,bsr->bhs", q_c, cache_ckv.astype(jnp.float32))
         + jnp.einsum("bhd,bsd->bhs", q_rope[:, :, 0].astype(jnp.float32),
                      cache_krope.astype(jnp.float32))) * scale
    mask = jnp.arange(cache_ckv.shape[1]) <= pos
    s = jnp.where(mask[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx_c = jnp.einsum("bhs,bsr->bhr", w, cache_ckv.astype(jnp.float32))
    out = jnp.einsum("bhr,rhv->bhv", ctx_c, w_v.astype(jnp.float32))
    out = out.reshape(B, 1, H * dv).astype(x.dtype)
    return out @ p["o"], cache_ckv, cache_krope
