"""State-space models: Mamba1 (selective scan) and Mamba2 (SSD).

Mamba1 mixes the sequence with a lax.scan over time that carries the
(B, d_inner, d_state) state: HBM traffic is O(seq) state round-trips, the
memory-bound term the Pallas kernel in repro.kernels.mamba_scan keeps in
VMEM.

Mamba2 uses the chunked SSD algorithm directly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models.layers import Leaf, dense_init, norm_init, rmsnorm


# ---------------------------------------------------------------------------
# init


def mamba1_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    dt_rank = max(1, math.ceil(d / 16))
    N = s.d_state
    r = jax.random.split(rng, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (d_in, 1))
    return {
        "in_proj": dense_init(r[0], d, 2 * d_in, ("d_model", "d_inner")),
        "conv_w": Leaf(jax.random.normal(r[1], (d_in, s.d_conv), jnp.float32)
                       .astype(dtype) * 0.2, ("d_inner", None)),
        "conv_b": Leaf(jnp.zeros((d_in,), dtype), ("d_inner",)),
        "x_proj": dense_init(r[2], d_in, dt_rank + 2 * N, ("d_inner", None)),
        "dt_proj": dense_init(r[3], dt_rank, d_in, (None, "d_inner")),
        "dt_bias": Leaf(jnp.full((d_in,), -4.6, jnp.float32), ("d_inner",)),
        "A_log": Leaf(jnp.log(A), ("d_inner", None)),
        "D": Leaf(jnp.ones((d_in,), jnp.float32), ("d_inner",)),
        "out_proj": dense_init(r[4], d_in, d, ("d_inner", "d_model")),
    }


def mamba2_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    H = s.n_heads
    N = s.d_state
    assert H * s.head_dim == d_in, (H, s.head_dim, d_in)
    r = jax.random.split(rng, 4)
    conv_dim = d_in + 2 * N  # conv over (x, B, C)
    return {
        "in_proj": dense_init(r[0], d, 2 * d_in + 2 * N + H,
                              ("d_model", "d_inner")),
        "conv_w": Leaf(jax.random.normal(r[1], (conv_dim, s.d_conv),
                                         jnp.float32).astype(dtype) * 0.2,
                       ("d_inner", None)),
        "conv_b": Leaf(jnp.zeros((conv_dim,), dtype), ("d_inner",)),
        "A_log": Leaf(jnp.zeros((H,), jnp.float32), (None,)),
        "dt_bias": Leaf(jnp.full((H,), -4.6, jnp.float32), (None,)),
        "D": Leaf(jnp.ones((H,), jnp.float32), (None,)),
        "norm": norm_init(d_in),
        "out_proj": dense_init(r[2], d_in, d, ("d_inner", "d_model")),
    }


# ---------------------------------------------------------------------------
# causal depthwise conv (kernel k, as sum of shifts — k is 4)


def causal_conv1d(x, w, b):
    """x: (B, S, C); w: (C, k); returns (B, S, C)."""
    k = w.shape[1]
    out = x * w[None, None, :, -1]
    for i in range(1, k):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[None, None, :, -1 - i]
    return out + b[None, None]


def conv1d_step(x_t, conv_state, w, b):
    """x_t: (B, C); conv_state: (B, C, k-1) past inputs.  Returns (y, state)."""
    k = w.shape[1]
    full = jnp.concatenate([conv_state, x_t[..., None]], axis=-1)  # (B,C,k)
    y = jnp.sum(full * w[None], axis=-1) + b[None]
    return y, full[..., 1:]


# ---------------------------------------------------------------------------
# mamba1 selective scan


def _ssm_coeffs1(p, xz, cfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    N = s.d_state
    dt_rank = p["dt_proj"].shape[0]
    x, z = xz[..., :d_in], xz[..., d_in:]
    x = causal_conv1d(x, p["conv_w"], p["conv_b"])
    x = jax.nn.silu(x)
    proj = x @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"]
                         + p["dt_bias"]).astype(jnp.float32)   # (B,S,d_in)
    Bm = proj[..., dt_rank:dt_rank + N].astype(jnp.float32)    # (B,S,N)
    Cm = proj[..., dt_rank + N:].astype(jnp.float32)           # (B,S,N)
    A = -jnp.exp(p["A_log"])                                   # (d_in,N)
    return x, z, dt, Bm, Cm, A


@jax.named_scope("ssm")
def mamba1_forward(p, x_seq, cfg: ModelConfig, state=None):
    """x_seq: (B, S, d_model) -> (out, final_state dict(conv, ssm)).

    state (decode carry): dict(conv (B,d_in,k-1), ssm (B,d_in,N)).
    """
    s = cfg.ssm
    B = x_seq.shape[0]
    d_in = s.expand * cfg.d_model
    N = s.d_state
    xz = x_seq @ p["in_proj"]
    # conv tail = last (k-1) pre-conv inputs, for decode continuation
    conv_tail = xz[:, -(s.d_conv - 1):, :d_in].transpose(0, 2, 1)
    x, z, dt, Bm, Cm, A = _ssm_coeffs1(p, xz, cfg)
    xf = x.astype(jnp.float32)

    da = jnp.exp(dt[..., None] * A[None, None])                # (B,S,d_in,N)
    dbx = dt[..., None] * Bm[:, :, None, :] * xf[..., None]    # (B,S,d_in,N)

    h0 = (jnp.zeros((B, d_in, N), jnp.float32) if state is None
          else state["ssm"])

    def step(h, inp):
        da_t, dbx_t, C_t = inp
        h = da_t * h + dbx_t
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y
    hT, ys = jax.lax.scan(
        step, h0,
        (da.transpose(1, 0, 2, 3), dbx.transpose(1, 0, 2, 3),
         Cm.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2)                                  # (B,S,d_in)

    y = y + p["D"][None, None] * xf
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_seq.dtype)
    return y @ p["out_proj"], {"ssm": hT,
                               "conv": conv_tail.astype(jnp.bfloat16)}


@jax.named_scope("ssm")
def mamba1_decode(p, x_t, state, cfg: ModelConfig):
    """One-token decode.  x_t: (B, 1, d).  state: dict(conv, ssm)."""
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    N = s.d_state
    dt_rank = p["dt_proj"].shape[0]
    xz = (x_t[:, 0] @ p["in_proj"])
    x, z = xz[..., :d_in], xz[..., d_in:]
    xc, conv_state = conv1d_step(x, state["conv"], p["conv_w"], p["conv_b"])
    xc = jax.nn.silu(xc)
    proj = xc @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"]
                         + p["dt_bias"]).astype(jnp.float32)   # (B,d_in)
    Bm = proj[..., dt_rank:dt_rank + N].astype(jnp.float32)
    Cm = proj[..., dt_rank + N:].astype(jnp.float32)
    A = -jnp.exp(p["A_log"])
    xf = xc.astype(jnp.float32)
    h = state["ssm"]
    h = jnp.exp(dt[..., None] * A[None]) * h \
        + dt[..., None] * Bm[:, None, :] * xf[..., None]
    y = jnp.einsum("bdn,bn->bd", h, Cm) + p["D"][None] * xf
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_t.dtype)
    return (y @ p["out_proj"])[:, None], {"conv": conv_state, "ssm": h}


# ---------------------------------------------------------------------------
# mamba2 (SSD, chunked)


@jax.named_scope("ssm")
def mamba2_forward(p, x_seq, cfg: ModelConfig, state=None):
    """x_seq: (B, S, d_model) -> (out, final ssm state (B,H,P,N))."""
    s = cfg.ssm
    B, S, _ = x_seq.shape
    d_in = s.expand * cfg.d_model
    H, P, N = s.n_heads, s.head_dim, s.d_state
    c = min(s.chunk, S)
    assert S % c == 0
    nc = S // c

    zxbcdt = x_seq @ p["in_proj"]
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_in + 2 * N]
    conv_tail = xbc[:, -(s.d_conv - 1):].transpose(0, 2, 1)
    dt = jax.nn.softplus(
        zxbcdt[..., -H:].astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    xbc = jax.nn.silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d_in].reshape(B, S, H, P)
    Bm = xbc[..., d_in:d_in + N].astype(jnp.float32)           # (B,S,N)
    Cm = xbc[..., d_in + N:].astype(jnp.float32)               # (B,S,N)
    A = -jnp.exp(p["A_log"])                                   # (H,)

    loga = dt * A[None, None]                                  # (B,S,H) <=0
    xf = x.astype(jnp.float32)

    # chunk views: (nc, B, c, ...)
    def chunks(t):
        return t.reshape(B, nc, c, *t.shape[2:]).transpose(1, 0, 2,
                                                           *range(3, t.ndim + 1))
    loga_c, x_c, B_c, C_c, dt_c = map(chunks, (loga, xf, Bm, Cm, dt))

    def chunk_step(h, inp):
        la, xi, bi, ci, dti = inp   # (B,c,H),(B,c,H,P),(B,c,N),(B,c,N),(B,c,H)
        cs = jnp.cumsum(la, axis=1)                            # (B,c,H)
        # intra-chunk: decay matrix L[i,j] = exp(cs_i - cs_j) for i >= j
        diff = cs[:, :, None, :] - cs[:, None, :, :]           # (B,c,c,H)
        tri = jnp.tril(jnp.ones((c, c), bool))
        L = jnp.where(tri[None, :, :, None], jnp.exp(diff), 0.0)
        cb = jnp.einsum("bin,bjn->bij", ci, bi)                # (B,c,c)
        w = cb[:, :, :, None] * L                              # (B,c,c,H)
        y_intra = jnp.einsum("bijh,bjh,bjhp->bihp", w, dti, xi)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bin,bhpn,bih->bihp", ci, h, jnp.exp(cs))
        # state update
        decay_to_end = jnp.exp(cs[:, -1:, :] - cs)             # (B,c,H)
        dx = dti[..., None] * xi * decay_to_end[..., None]     # (B,c,H,P)
        h_new = h * jnp.exp(cs[:, -1])[:, :, None, None] \
            + jnp.einsum("bchp,bcn->bhpn", dx, bi)
        return h_new, y_intra + y_inter

    h0 = (jnp.zeros((B, H, P, N), jnp.float32) if state is None
          else state["ssm"])
    hT, ys = jax.lax.scan(chunk_step, h0, (loga_c, x_c, B_c, C_c, dt_c))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    y = y + p["D"][None, None, :, None] * xf
    y = y.reshape(B, S, d_in)
    y = rmsnorm(y.astype(x_seq.dtype), p["norm"])
    y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    return y.astype(x_seq.dtype) @ p["out_proj"], \
        {"ssm": hT, "conv": conv_tail.astype(jnp.bfloat16)}


@jax.named_scope("ssm")
def mamba2_decode(p, x_t, state, cfg: ModelConfig):
    """One-token decode.  state: dict(conv (B,conv_dim,k-1), ssm (B,H,P,N))."""
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    H, P, N = s.n_heads, s.head_dim, s.d_state
    zxbcdt = x_t[:, 0] @ p["in_proj"]
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_in + 2 * N]
    dt = jax.nn.softplus(zxbcdt[..., -H:].astype(jnp.float32) + p["dt_bias"])
    xc, conv_state = conv1d_step(xbc, state["conv"], p["conv_w"], p["conv_b"])
    xc = jax.nn.silu(xc)
    x = xc[..., :d_in].reshape(-1, H, P).astype(jnp.float32)
    Bm = xc[..., d_in:d_in + N].astype(jnp.float32)
    Cm = xc[..., d_in + N:].astype(jnp.float32)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A[None])                                  # (B,H)
    h = state["ssm"] * a[..., None, None] \
        + jnp.einsum("bh,bhp,bn->bhpn", dt, x, Bm)
    y = jnp.einsum("bhpn,bn->bhp", h, Cm) + p["D"][None, :, None] * x
    y = y.reshape(-1, d_in)
    y = rmsnorm(y.astype(x_t.dtype), p["norm"])
    y = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    return (y.astype(x_t.dtype) @ p["out_proj"])[:, None], \
        {"conv": conv_state, "ssm": h}
