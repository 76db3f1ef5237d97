"""The model path's one implementation of each mechanism against a plain
reference written apart from it, at smoke size on the CPU:

  * ``chunked_attention``, the prefill and training attention: causal,
    with and without a sliding window that binds, over a key length that
    is not a multiple of the chunk; its output and its gradient with
    respect to the queries against a masked softmax over the whole
    score matrix;
  * ``mamba1_forward``, the Mamba1 scan: against a Python loop over time
    of h = exp(dt A) h + dt B x, y = C h + D x, from the same weights;
  * ``_moe_local_shard``, the one MoE path: split over 2 and 4 ranks of
    expert parallelism, each rank with its slice of the experts, the
    ranks' outputs add up to the unsplit layer.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import moe as M
from repro.models.attention import chunked_attention
from repro.models.layers import split_leaves
from repro.models.ssm import mamba1_forward, mamba1_init


def _plain_attention(q, k, v, window):
    """Causal softmax attention over the whole (Sq, Skv) score matrix,
    each K/V head repeated for its query heads; ``window`` 0: unlimited."""
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = jnp.arange(q.shape[2])
    mask = t[:, None] >= t[None, :]
    if window:
        mask &= t[:, None] - t[None, :] < window
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("window", [0, 5], ids=["full", "window5"])
def test_chunked_attention_matches_masked_softmax(window):
    B, H, Hkv, S, D, chunk = 2, 4, 2, 20, 16, 8     # 3 chunks, the last padded
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k, v = (jax.random.normal(r, (B, Hkv, S, D)) for r in ks[1:3])
    ct = jax.random.normal(ks[3], (B, H, S, D))     # the output's cotangent

    def chunked(q):
        return chunked_attention(q, k, v, causal=True, window=window,
                                 chunk=chunk)

    def plain(q):
        return _plain_attention(q, k, v, window)

    with jax.default_matmul_precision("highest"):
        got, want = chunked(q), plain(q)
        g_got = jax.grad(lambda q: jnp.sum(chunked(q) * ct))(q)
        g_want = jax.grad(lambda q: jnp.sum(plain(q) * ct))(q)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(g_got, g_want, rtol=1e-4, atol=1e-5)
    if window:      # the window binds: it is not the full causal result
        assert not np.allclose(got, _plain_attention(q, k, v, 0), atol=1e-3)


def _softplus(x):
    return np.logaddexp(0.0, x)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _plain_mamba1(p, x, cfg):
    """Mamba1 in float64, one time step at a time: (out, h, conv tail)."""
    s = cfg.ssm
    d_in, N, K = s.expand * cfg.d_model, s.d_state, s.d_conv
    p = {n: np.asarray(a, np.float64) for n, a in p.items()}
    R = p["dt_proj"].shape[0]
    xz = np.asarray(x, np.float64) @ p["in_proj"]
    u, z = xz[..., :d_in], xz[..., d_in:]
    B_, S = u.shape[:2]
    A = -np.exp(p["A_log"])                                     # (d_in, N)
    h = np.zeros((B_, d_in, N))
    ys = []
    for t in range(S):
        # causal depthwise conv: tap K-1-j weighs the input j steps back
        c = p["conv_b"] + sum(p["conv_w"][:, K - 1 - j] * u[:, t - j]
                              for j in range(K) if t - j >= 0)
        xt = _silu(c)                                           # (B, d_in)
        proj = xt @ p["x_proj"]
        dt = _softplus(proj[:, :R] @ p["dt_proj"] + p["dt_bias"])
        Bt, Ct = proj[:, R:R + N], proj[:, R + N:]
        h = (np.exp(dt[..., None] * A) * h
             + dt[..., None] * Bt[:, None, :] * xt[..., None])
        y = np.einsum("bdn,bn->bd", h, Ct) + p["D"] * xt
        ys.append(y * _silu(z[:, t]))
    out = np.stack(ys, axis=1) @ p["out_proj"]
    return out, h, u[:, S - (K - 1):].transpose(0, 2, 1)


def test_mamba1_scan_matches_a_loop_over_time():
    cfg = get_smoke_config("falcon_mamba_7b")
    p, _ = split_leaves(mamba1_init(jax.random.PRNGKey(0), cfg))
    p = {n: a.astype(jnp.float32) for n, a in p.items()}
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, state = mamba1_forward(p, x, cfg)
    want, h, tail = _plain_mamba1(p, x, cfg)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state["ssm"], h, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state["conv"], np.float32), tail,
                               rtol=1e-2, atol=1e-2)     # stored as bf16


@pytest.mark.parametrize("ep_size", [2, 4])
def test_moe_shares_add_up_to_the_unsplit_layer(ep_size):
    cfg = get_smoke_config("granite_moe_1b_a400m")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=8.0))         # no token dropped at any rank
    e = cfg.moe
    p, _ = split_leaves(M.moe_init(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    whole, aux = M._moe_local_shard(p, x, cfg, 0, 1, None)
    e_local = e.n_experts // ep_size
    total = jnp.zeros_like(whole)
    for rank in range(ep_size):
        mine = slice(rank * e_local, (rank + 1) * e_local)
        pl = dict(p, **{n: p[n][mine] for n in ("gate", "up", "down")})
        share, aux_r = M._moe_local_shard(pl, x, cfg, rank, ep_size, None)
        assert np.abs(np.asarray(share)).max() > 0, rank
        for n in aux:                       # routing is the same at every rank
            np.testing.assert_allclose(aux_r[n], aux[n], rtol=1e-6)
        total = total + share
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
