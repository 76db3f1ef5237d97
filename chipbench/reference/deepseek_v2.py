"""Plain float32 reference of DeepSeek-V2 over served requests, written
from the paper (arXiv:2405.04434) and the published ``modeling_deepseek.py``
and independent of ``repro``: it imports nothing of the program and reads
its weights from ``chipbench.weights_mla``, regenerated from the seed one
layer at a time.

For each prompt with its served tokens it gives the float32 logits at
every position that produced a served token, layer by layer over all the
sequences, so that the whole model never sits on one device in float32:
  * RMSNorm (epsilon from the file) before attention, before the FFN, on
    the latent and before the untied head;
  * latent attention, naive: ``c_kv`` = RMSNorm of the first R outputs of
    ``kv_a``, projected up by ``kv_b`` to per-head keys (``qk_nope``) and
    values; the rope key (``kv_a``'s last ``qk_rope`` outputs) is shared by
    all heads; causal softmax at ``(qk_nope + qk_rope) ** -0.5`` times
    ``yarn_get_mscale(factor, mscale_all_dim) ** 2``;
  * YaRN rope (DeepseekV2YarnRotaryEmbedding) on the ``qk_rope`` dims,
    rotating halves of the dims as stored (the published code first
    de-interleaves them: with random weights, a permutation of columns);
  * the first ``first_k_dense_replace`` layers a SwiGLU MLP of
    ``intermediate_size``; the others softmax routing over all experts, the
    top-k probabilities unnormalised (``norm_topk_prob`` false) times
    ``routed_scaling_factor``, every expert computed for every token and
    masked by its weight, with no capacity limit, plus the shared experts.

Every product runs at ``Precision.HIGHEST`` in float32.  ``low="fp8"``
rounds every operand of every product to float8 (e4m3) first, as
``chipbench.reference.model`` does: the control.  ``low="bf16"`` rounds
them to bfloat16 instead, the precision the model is served in: a witness
of how far bfloat16 products alone move the served tokens.  ``drop_experts``
leaves the given routed experts out of every MoE layer: the fault of one
chip's share of the experts missing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights_mla
from chipbench.reference.model import _fp8, rmsnorm
from chipbench.weights import base_key

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, axis, low):
    """x rounded to the precision ``low`` names, kept in float32."""
    if low == "fp8":
        return _fp8(x, axis)
    if low == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def mm(a, b, low=None):
    """a (..., K) @ b (K, N), each operand first rounded to ``low``."""
    return jnp.matmul(_round(a, -1, low), _round(b, 0, low),
                      precision=HIGHEST)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(m) -> np.ndarray:
    dim, theta = m["dr"], m["theta"]
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)

    def correction_dim(rotations):
        return (dim * math.log(m["yarn_original"] / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(m["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / m["yarn_factor"] * ramp + extra * (1 - ramp)


def _rope(x, m):
    """x (S, ..., dr), rotated by position 0 .. S-1."""
    S, dr = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(yarn_inv_freq(m),
                                                          F32)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (dr // 2,))
    scale = (_mscale(m["yarn_factor"], m["yarn_mscale"])
             / _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]))
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(x, w, m, low):
    """One sequence: x (S, d) -> (S, d), causal."""
    S, H, R = x.shape[0], m["H"], m["R"]
    dn, dr, dv = m["dn"], m["dr"], m["dv"]
    q = mm(x, w["attn.q"], low).reshape(S, H, dn + dr)
    kv = mm(x, w["attn.kv_a"], low)
    c = rmsnorm(kv[:, :R], w["attn.kv_norm"], m["eps"])
    kvb = mm(c, w["attn.kv_b"], low).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m)], -1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        _rope(kv[:, R:], m)[:, None], (S, H, dr))], -1)
    v = kvb[..., dn:]
    q, k, v = _round(q, -1, low), _round(k, -1, low), _round(v, 0, low)
    scale = ((dn + dr) ** -0.5
             * _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]) ** 2)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    p = _round(p, -1, low)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return mm(o.reshape(S, H * dv), w["attn.o"], low)


def swiglu(x, gate, up, down, low):
    return mm(jax.nn.silu(mm(x, gate, low)) * mm(x, up, low), down, low)


def moe(x, w, m, low, keep):
    """x (T, d) -> (T, d); ``keep`` (E,) is 1 for each expert that
    computes, 0 for one left out."""
    probs = jax.nn.softmax(mm(x, w["moe.router"], low), -1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if m["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    else:
        top = top * m["route_scale"]
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None],
                                    idx].set(top) * keep

    def expert(acc, e):
        y = swiglu(x, w["moe.gate"][e], w["moe.up"][e], w["moe.down"][e],
                   low)
        return acc + gate[:, e, None] * y, None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m["E"]))
    return out + swiglu(x, w["moe.shared.gate"], w["moe.shared.up"],
                        w["moe.shared.down"], low)


@functools.partial(jax.jit, static_argnames=("m", "low"))
def _layer(w, h, keep, m, low):
    """One layer over a batch of sequences h (n, S, d)."""
    m = dict(m)
    n, S, d = h.shape
    h = h + jax.lax.map(lambda x: attention(x, w, m, low),
                        rmsnorm(h, w["norm1"], m["eps"]))
    x = rmsnorm(h, w["norm2"], m["eps"]).reshape(n * S, d)
    if "moe.router" in w:
        y = moe(x, w, m, low, keep)
    else:
        y = swiglu(x, w["mlp.gate"], w["mlp.up"], w["mlp.down"], low)
    return h + y.reshape(n, S, d)


@functools.partial(jax.jit, static_argnames=("m", "low", "first"))
def _head(g, h, m, low, first):
    m = dict(m)
    return mm(rmsnorm(h[:, first:], g["final_norm"], m["eps"]), g["lm_head"],
              low)


def served_logits(spec: dict, seed: int, prompts: np.ndarray,
                  served: np.ndarray, low: str | None = None,
                  drop_experts=()) -> jax.Array:
    """prompts (n, P), served (n, N) -> logits (n, N, V): position P-1+j
    of prompt + served[:-1] predicts served[:, j]."""
    with jax.default_matmul_precision("highest"):
        d = weights_mla.dims(spec)
        m = tuple(sorted(d.items()))
        seqs = np.concatenate([prompts, served[:, :-1]], 1)
        keep = jnp.ones((d["E"],), F32).at[jnp.asarray(
            list(drop_experts), jnp.int32)].set(0.0)
        g = weights_mla.globals_f32(spec, seed)
        key = base_key(seed)
        layer_w = weights_mla.layer_f32(spec)
        h = g["embed"][jnp.asarray(seqs)]
        for i in range(d["L"]):
            h = _layer(layer_w(key, i), h, keep, m=m, low=low)
        return _head(g, h, m=m, low=low, first=prompts.shape[1] - 1)
