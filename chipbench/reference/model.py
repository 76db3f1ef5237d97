"""Plain float32 reference of the decoder that the configuration files
declare, written from the published equations and independent of
``repro``: it imports nothing of the program and reads its weights from
``chipbench.weights``, regenerated from the seed.

It implements each configuration as the repository runs it: RMSNorm
(epsilon from the file), rotary embeddings on the two halves of each head
(the ``rotate_half`` convention), grouped-query causal softmax attention
scaled by ``head_dim ** -0.5``, and a SwiGLU MLP, or top-k routing (softmax
over all experts, the k largest renormalised to sum to one) over SwiGLU
experts that every routed token reaches, with no capacity limit; logits
from the tied embedding or the LM head.  Where the published model
differs (granite's multipliers, Phi-3's sliding window), the configuration
file lists it.

Every matrix product runs at ``Precision.HIGHEST`` in float32.  With
``fp8=True`` every operand of every product is first rounded to float8
(e4m3) with one scale per row of the left operand and per column of the
right: the control, the same model computed in the precision below the
bfloat16 the configurations serve in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(a, b, fp8=False):
    """a (..., K) @ b (K, N)."""
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def rmsnorm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, pos, theta):
    """x (S, heads, hd); pos (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(x, w, m, fp8):
    """One sequence: x (S, d) -> (S, d), causal."""
    S = x.shape[0]
    H, Hkv, hd = m["H"], m["Hkv"], m["hd"]
    pos = jnp.arange(S)
    q = rope(mm(x, w["attn.q"], fp8).reshape(S, H, hd), pos, m["theta"])
    k = rope(mm(x, w["attn.k"], fp8).reshape(S, Hkv, hd), pos, m["theta"])
    v = mm(x, w["attn.v"], fp8).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    return mm(o.reshape(S, H * hd), w["attn.o"], fp8)


def swiglu(x, gate, up, down, fp8):
    return mm(jax.nn.silu(mm(x, gate, fp8)) * mm(x, up, fp8), down, fp8)


def route(x, router, k, fp8):
    """(weights (T, E) with the top-k renormalised and zeros elsewhere,
    router logits, top-1 expert)."""
    logits = mm(x, router, fp8)
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    T = x.shape[0]
    dense = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], idx].set(top)
    return dense, logits, probs, idx[:, 0]


def moe(x, w, m, fp8):
    """x (T, d) -> (out (T, d), load-balance term, router z term)."""
    dense, logits, probs, top1 = route(x, w["moe.router"], m["k"], fp8)

    @jax.checkpoint   # the backward pass keeps one expert's work at a time
    def expert(acc, e):
        y = swiglu(x, w["moe.gate"][e], w["moe.up"][e], w["moe.down"][e], fp8)
        return acc + dense[:, e, None] * y, None
    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m["E"]))
    ce = jnp.mean(jax.nn.one_hot(top1, m["E"], dtype=F32), 0)
    lb = m["E"] * jnp.sum(jnp.mean(probs, 0) * ce)
    rz = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return out, lb, rz


def layer(w, h, m, fp8=False):
    """One decoder layer over a batch of sequences h (n, S, d).  Returns
    (h, load-balance term, router z term), the last two zero when dense."""
    n, S, d = h.shape
    a = jax.lax.map(jax.checkpoint(lambda x: attention(x, w, m, fp8)),
                    rmsnorm(h, w["norm1"], m["eps"]))
    h = h + a
    x = rmsnorm(h, w["norm2"], m["eps"]).reshape(n * S, d)
    if m["E"]:
        y, lb, rz = moe(x, w, m, fp8)
    else:
        y = swiglu(x, w["mlp.gate"], w["mlp.up"], w["mlp.down"], fp8)
        lb = rz = jnp.zeros((), F32)
    return h + y.reshape(n, S, d), lb, rz


def logits(g, h, m, fp8=False):
    """Final norm and head: h (..., d) -> (..., V)."""
    x = rmsnorm(h, g["final_norm"], m["eps"])
    head = g["embed"].T if m["tied"] else g["lm_head"]
    return mm(x, head, fp8)
