"""Model builder: decoder-only / enc-dec / SSM / hybrid transformers.

All architectures share the same entry points:
  init_params(cfg, rng)                  -> (params, axes)
  train_forward(cfg, params, batch)      -> (logits, aux)
  loss_fn(cfg, params, batch)            -> (loss, metrics)
  init_cache(cfg, batch, max_seq)        -> (cache, cache_axes)
  prefill_forward(cfg, params, batch)    -> (logits_last, cache)
  decode_forward(cfg, params, cache, tokens, pos) -> (logits, cache)

Layers are STACKED along a leading axis and executed with lax.scan (+remat),
which keeps HLO size O(1) in depth and forms the loop tree the SMAUG-style
sampled simulator unsamples through.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (Leaf, apply_norm, embed_init, mlp_apply,
                                 mlp_init, norm_init, rope_tables,
                                 sinusoid_positions, split_leaves)

Pytree = Any


# ---------------------------------------------------------------------------
# init


def _block_init(rng, cfg: ModelConfig, kind: str):
    """kind: attn_mlp | attn_moe | xattn (encdec decoder) | mamba1 | mamba2"""
    r = jax.random.split(rng, 6)
    if kind == "mamba1":
        return {"norm1": norm_init(cfg.d_model),
                "ssm": ssm_mod.mamba1_init(r[0], cfg)}
    if kind == "mamba2":
        return {"norm1": norm_init(cfg.d_model),
                "ssm": ssm_mod.mamba2_init(r[0], cfg)}
    p = {"norm1": norm_init(cfg.d_model),
         "attn": attn.attn_init(r[0], cfg),
         "norm2": norm_init(cfg.d_model)}
    if kind == "attn_moe":
        p["moe"] = moe_mod.moe_init(r[1], cfg)
    else:
        p["mlp"] = mlp_init(r[1], cfg.d_model, cfg.d_ff, cfg.activation)
    if kind == "xattn":
        p["norm_x"] = norm_init(cfg.d_model)
        p["xattn"] = attn.attn_init(r[2], cfg)
        p["mlp"] = mlp_init(r[3], cfg.d_model, cfg.d_ff, cfg.activation)
    return p


def _layer_kind(cfg: ModelConfig) -> str:
    if cfg.family == "ssm":
        return "mamba1" if cfg.ssm.version == 1 else "mamba2"
    if cfg.family == "hybrid":
        return "mamba2" if cfg.ssm.version == 2 else "mamba1"
    if cfg.family == "moe":
        return "attn_moe"
    if cfg.family == "encdec":
        return "xattn"
    return "attn_mlp"


def _stack_init(rng, cfg: ModelConfig, kind: str, n: int):
    rngs = jax.random.split(rng, n)
    leaves = [_block_init(r, cfg, kind) for r in rngs]

    def is_leaf(x):
        return isinstance(x, Leaf)

    def stack(*ls):
        return Leaf(jnp.stack([l.value for l in ls]),
                    ("layers",) + ls[0].axes)
    return jax.tree_util.tree_map(stack, *leaves, is_leaf=is_leaf)


def init_params(cfg: ModelConfig, rng) -> Tuple[Pytree, Pytree]:
    """Returns (params, logical-axes tree)."""
    r = jax.random.split(rng, 6)
    kind = _layer_kind(cfg)
    p: Dict[str, Any] = {"embed": embed_init(r[0], cfg.vocab, cfg.d_model)}
    nd = cfg.n_dense_layers
    if nd:
        r_dense, r_layers = jax.random.split(r[1])
        p["dense_layers"] = _stack_init(r_dense, cfg, "attn_mlp", nd)
        p["layers"] = _stack_init(r_layers, cfg, kind, cfg.n_layers - nd)
    else:
        p["layers"] = _stack_init(r[1], cfg, kind, cfg.n_layers)
    p["final_norm"] = norm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        from repro.models.layers import dense_init
        p["lm_head"] = dense_init(r[2], cfg.d_model, cfg.vocab,
                                  ("d_model", "vocab"))
    if cfg.family == "encdec":
        p["encoder"] = {
            "layers": _stack_init(r[3], cfg, "attn_mlp", cfg.encoder.n_layers),
            "final_norm": norm_init(cfg.d_model),
        }
        n_pos = min(cfg.max_seq, 32_768)
        p["pos"] = Leaf(
            (jax.random.normal(r[4], (n_pos, cfg.d_model), jnp.float32)
             * 0.01).astype(jnp.bfloat16), (None, "d_model"))
    if cfg.family == "hybrid":
        p["shared_attn"] = _block_init(r[5], cfg, "attn_mlp")
    return split_leaves(p)


# ---------------------------------------------------------------------------
# helpers


ZERO_AUX = lambda: (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))


def _stacks(cfg: ModelConfig, p):
    """The stacked layers in order, as (stack, first layer, layers): the
    leading dense layers where the config has them, then ``layers``."""
    nd = cfg.n_dense_layers
    if not nd:
        return [(p["layers"], 0, cfg.n_layers)]
    return [(p["dense_layers"], 0, nd), (p["layers"], nd, cfg.n_layers - nd)]


def _concat_layers(parts):
    """Per-stack outputs stacked on the layer axis, joined in layer order."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *parts)


def _window_schedule(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer window sizes; 0 = full attention."""
    L = cfg.n_layers
    if cfg.local_global_ratio > 0:
        k = cfg.local_global_ratio + 1
        return jnp.array([0 if (i + 1) % k == 0 else cfg.window
                          for i in range(L)], jnp.int32)
    return jnp.full((L,), cfg.window, jnp.int32)


@jax.named_scope("attention")
def _rope_for(cfg: ModelConfig, start, n: int):
    """RoPE tables of positions ``start`` .. ``start + n - 1``."""
    if cfg.rope_theta <= 0:
        return None, None
    dim = cfg.mla.qk_rope_dim if cfg.mla is not None else cfg.resolved_head_dim
    return rope_tables(start + jnp.arange(n), dim, cfg.rope_theta, cfg.yarn)


@jax.named_scope("embed")
def _embed_tokens(cfg: ModelConfig, p, tokens, pos_offset=0):
    x = p["embed"][tokens]
    if cfg.family == "encdec":
        pe = jax.lax.dynamic_slice_in_dim(p["pos"], pos_offset,
                                          tokens.shape[1], 0)
        x = x + pe[None]
    if cfg.family in ("dense", "vlm", "moe") and cfg.name.startswith("gemma"):
        x = x * (cfg.d_model ** 0.5)  # gemma embeds are scaled
    return x.astype(jnp.bfloat16)


@jax.named_scope("logits")
def _logits(cfg: ModelConfig, p, x):
    x = apply_norm(cfg.norm, x, p["final_norm"])
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, p["embed"])
    return x @ p["lm_head"]


@jax.named_scope("layers")
def _encoder_forward(cfg: ModelConfig, p, frames):
    """frames: (B, n_ctx, d) precomputed (frontend stub).  Whisper encoder."""
    x = frames.astype(jnp.float32) \
        + sinusoid_positions(frames.shape[1], cfg.d_model)[None]
    x = x.astype(jnp.bfloat16)

    def body(x, pl):
        h, _ = attn.gqa_forward(pl["attn"],
                                apply_norm(cfg.norm, x, pl["norm1"]),
                                None, None, cfg=cfg, causal=False)
        x = x + h
        h = mlp_apply(pl["mlp"], apply_norm(cfg.norm, x, pl["norm2"]),
                      cfg.activation)
        return x + h, ()

    x, _ = jax.lax.scan(jax.checkpoint(body), x, p["encoder"]["layers"])
    return apply_norm(cfg.norm, x, p["encoder"]["final_norm"])


# ---------------------------------------------------------------------------
# backbone (full-sequence; train and prefill)


@jax.named_scope("layers")
def _backbone(cfg: ModelConfig, p, x, xa=None, collect=False):
    """Returns (x, aux(lb, rz), collected-states dict or None)."""
    cos, sin = _rope_for(cfg, 0, x.shape[1])

    if cfg.family == "ssm":
        fwd = (ssm_mod.mamba1_forward if cfg.ssm.version == 1
               else ssm_mod.mamba2_forward)

        def body(x, pl):
            h, st = fwd(pl["ssm"], apply_norm(cfg.norm, x, pl["norm1"]), cfg)
            return x + h, (st if collect else ())
        x, sts = jax.lax.scan(jax.checkpoint(body), x, p["layers"])
        return x, ZERO_AUX(), (sts if collect else None)

    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        nsb = cfg.n_layers // k
        shared = p["shared_attn"]

        def superblock(x, pls):
            def mamba_body(x, pl):
                h, st = ssm_mod.mamba2_forward(
                    pl["ssm"], apply_norm(cfg.norm, x, pl["norm1"]), cfg)
                return x + h, (st if collect else ())
            x, sts = jax.lax.scan(jax.checkpoint(mamba_body), x, pls)
            h, kv = attn.gqa_forward(shared["attn"],
                                     apply_norm(cfg.norm, x, shared["norm1"]),
                                     cos, sin, cfg=cfg, causal=True)
            x = x + h
            h = mlp_apply(shared["mlp"],
                          apply_norm(cfg.norm, x, shared["norm2"]),
                          cfg.activation)
            return x + h, ((sts, kv) if collect else ())

        pls = jax.tree_util.tree_map(
            lambda t: t.reshape(nsb, k, *t.shape[1:]), p["layers"])
        x, ys = jax.lax.scan(jax.checkpoint(superblock), x, pls)
        return x, ZERO_AUX(), (ys if collect else None)

    windows = _window_schedule(cfg)

    def body(carry, xs):
        x, lb, rz = carry
        pl, window = xs
        h_in = apply_norm(cfg.norm, x, pl["norm1"])
        if cfg.mla is not None:
            h, kv = attn.mla_forward(pl["attn"], h_in, cos, sin, cfg=cfg)
        else:
            h, kv = attn.gqa_forward(pl["attn"], h_in, cos, sin, cfg=cfg,
                                     causal=True, window=window)
        x = x + h
        xkv = ()
        if xa is not None:
            h, xkv = attn.gqa_forward(pl["xattn"],
                                      apply_norm(cfg.norm, x, pl["norm_x"]),
                                      None, None, cfg=cfg, causal=False,
                                      xa=xa)
            x = x + h
        h_in = apply_norm(cfg.norm, x, pl["norm2"])
        if "moe" in pl:
            h, aux = moe_mod.moe_apply(pl["moe"], h_in, cfg)
            lb, rz = lb + aux["load_balance"], rz + aux["router_z"]
        else:
            h = mlp_apply(pl["mlp"], h_in, cfg.activation)
        return (x + h, lb, rz), ((kv, xkv) if collect else ())

    lb0, rz0 = ZERO_AUX()
    carry, ys = (x, lb0, rz0), []
    for stack, first, n in _stacks(cfg, p):
        w = windows if n == cfg.n_layers else windows[first:first + n]
        carry, ys_stack = jax.lax.scan(jax.checkpoint(body), carry,
                                       (stack, w))
        ys.append(ys_stack)
    x, lb, rz = carry
    L = cfg.n_layers
    return x, (lb / L, rz / L), (_concat_layers(ys) if collect else None)


# ---------------------------------------------------------------------------
# train


def _prepare_inputs(cfg: ModelConfig, params, batch):
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens)
    xa = None
    if cfg.family == "encdec":
        xa = _encoder_forward(cfg, params, batch["frames"])
    if cfg.family == "vlm":
        with jax.named_scope("embed"):
            x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    return x, xa


def train_forward(cfg: ModelConfig, params, batch):
    x, xa = _prepare_inputs(cfg, params, batch)
    x, (lb, rz), _ = _backbone(cfg, params, x, xa=xa)
    with jax.named_scope("logits"):     # of the text positions alone
        if cfg.family == "vlm":
            x = x[:, cfg.n_patches:]
        logits = _logits(cfg, params, x)
    return logits, {"load_balance": lb, "router_z": rz}


def loss_fn(cfg: ModelConfig, params, batch):
    logits, aux = train_forward(cfg, params, batch)
    with jax.named_scope("loss"):
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(logits, labels[..., None],
                                          axis=-1)[..., 0]
        nll = jnp.mean(logz - label_logit)
        zloss = 1e-4 * jnp.mean(logz ** 2)
        moe_loss = jnp.zeros((), jnp.float32)
        if cfg.moe is not None:
            moe_loss = (cfg.moe.aux_loss_coef * aux["load_balance"]
                        + cfg.moe.router_z_coef * aux["router_z"])
        loss = nll + zloss + moe_loss
    metrics = {"loss": loss, "nll": nll, "zloss": zloss, "moe_loss": moe_loss}
    return loss, metrics


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Returns (cache, logical-axes tree)."""
    L, hd = cfg.n_layers, cfg.resolved_head_dim
    Hkv = cfg.n_kv_heads
    c: Dict[str, Any] = {}

    def kv_leaf(n_layers, seq, axes_seq="kv_seq"):
        # "head_dim" is shardable as the MQA fallback (see dist.sharding)
        return Leaf(jnp.zeros((n_layers, batch, Hkv, seq, hd), jnp.bfloat16),
                    ("layers", "batch", "kv_heads", axes_seq, "head_dim"))

    if cfg.family == "ssm":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        conv_dim = d_in if s.version == 1 else d_in + 2 * s.d_state
        c["conv"] = Leaf(jnp.zeros((L, batch, conv_dim, s.d_conv - 1),
                                   jnp.bfloat16),
                         ("layers", "batch", "d_inner", None))
        if s.version == 1:
            c["ssm"] = Leaf(jnp.zeros((L, batch, d_in, s.d_state),
                                      jnp.float32),
                            ("layers", "batch", "d_inner", None))
        else:
            c["ssm"] = Leaf(jnp.zeros((L, batch, s.n_heads, s.head_dim,
                                       s.d_state), jnp.float32),
                            ("layers", "batch", "ssm_heads", None, None))
    elif cfg.family == "hybrid":
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nsb = L // cfg.hybrid_attn_every
        conv_dim = d_in + 2 * s.d_state
        c["conv"] = Leaf(jnp.zeros((L, batch, conv_dim, s.d_conv - 1),
                                   jnp.bfloat16),
                         ("layers", "batch", "d_inner", None))
        c["ssm"] = Leaf(jnp.zeros((L, batch, s.n_heads, s.head_dim,
                                   s.d_state), jnp.float32),
                        ("layers", "batch", "ssm_heads", None, None))
        c["k"] = kv_leaf(nsb, max_seq)
        c["v"] = kv_leaf(nsb, max_seq)
    elif cfg.mla is not None:
        m = cfg.mla
        c["ckv"] = Leaf(jnp.zeros((L, batch, max_seq, m.kv_lora_rank),
                                  jnp.bfloat16),
                        ("layers", "batch", "kv_seq", "kv_lora"))
        c["krope"] = Leaf(jnp.zeros((L, batch, max_seq, m.qk_rope_dim),
                                    jnp.bfloat16),
                          ("layers", "batch", "kv_seq", None))
    else:
        c["k"] = kv_leaf(L, max_seq)
        c["v"] = kv_leaf(L, max_seq)
        if cfg.family == "encdec":
            c["xk"] = kv_leaf(L, cfg.encoder.n_ctx, axes_seq=None)
            c["xv"] = kv_leaf(L, cfg.encoder.n_ctx, axes_seq=None)
    return split_leaves(c)


def prefill_forward(cfg: ModelConfig, params, batch,
                    max_seq: Optional[int] = None):
    """Runs the full prompt, returns (last-token logits, filled cache)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    prompt_len = S + (cfg.n_patches if cfg.family == "vlm" else 0)
    max_seq = max(max_seq or prompt_len, prompt_len)
    x, xa = _prepare_inputs(cfg, params, batch)
    x, _, collected = _backbone(cfg, params, x, xa=xa, collect=True)
    cache = _prefill_cache(cfg, collected, B, max_seq)
    # note: a VLM's patch prefix occupies cache positions [0, n_patches)
    with jax.named_scope("logits"):     # of the last position alone
        logits = _logits(cfg, params, x[:, -1:])
    return logits, cache


@jax.named_scope("kv_cache")
def _prefill_cache(cfg: ModelConfig, collected, B: int, max_seq: int):
    """The cache of ``max_seq`` positions holding the states the prefill's
    layer stack collected."""
    cache, _ = init_cache(cfg, B, max_seq)
    if cfg.family == "ssm":
        cache["conv"] = collected["conv"].astype(cache["conv"].dtype)
        cache["ssm"] = collected["ssm"]
    elif cfg.family == "hybrid":
        sts, kv = collected
        cache["conv"] = sts["conv"].reshape(cache["conv"].shape).astype(
            cache["conv"].dtype)
        cache["ssm"] = sts["ssm"].reshape(cache["ssm"].shape)
        k, v = kv
        cache["k"] = _fill_kv(cache["k"], k)
        cache["v"] = _fill_kv(cache["v"], v)
    elif cfg.mla is not None:
        kv, _ = collected
        ckv, krope = kv                                # (L,B,S,·)
        cache["ckv"] = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, 0, 0, 0))
        cache["krope"] = jax.lax.dynamic_update_slice(
            cache["krope"], krope.astype(cache["krope"].dtype), (0, 0, 0, 0))
    else:
        kv, xkv = collected
        cache["k"] = _fill_kv(cache["k"], kv[0])
        cache["v"] = _fill_kv(cache["v"], kv[1])
        if cfg.family == "encdec":
            cache["xk"] = xkv[0].astype(cache["xk"].dtype)
            cache["xv"] = xkv[1].astype(cache["xv"].dtype)
    return cache


def _fill_kv(cache_kv, new):
    return jax.lax.dynamic_update_slice(
        cache_kv, new.astype(cache_kv.dtype), (0,) * cache_kv.ndim)


# ---------------------------------------------------------------------------
# decode


def decode_forward(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step.  tokens: (B, 1); pos: scalar position (traced ok).
    Returns (logits (B, 1, V), new cache)."""
    x = _embed_tokens(cfg, params, tokens, pos)
    x, cache = _decode_layers(cfg, params, cache, x, pos)
    return _logits(cfg, params, x), cache


@jax.named_scope("layers")
def _decode_layers(cfg: ModelConfig, params, cache, x, pos):
    """The layer stack of one decode step: (x, new cache)."""
    cos, sin = _rope_for(cfg, pos, 1)

    if cfg.family == "ssm":
        dec = (ssm_mod.mamba1_decode if cfg.ssm.version == 1
               else ssm_mod.mamba2_decode)

        def body(x, xs):
            pl, conv, st = xs
            h, new = dec(pl["ssm"], apply_norm(cfg.norm, x, pl["norm1"]),
                         {"conv": conv, "ssm": st}, cfg)
            return x + h, (new["conv"], new["ssm"])
        x, (conv, st) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        return x, dict(cache, conv=conv, ssm=st)

    # Each layer reads its slice of the closed-over K/V cache and emits
    # only the new position's entries; one write after the scan puts all
    # of them into the cache.  Scanning the cache as xs would return it as
    # a stacked output, written whole and copied back every step.  A
    # recurrent state (conv, ssm) is replaced whole each step, so it is
    # scanned.
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        nsb = cfg.n_layers // k
        shared = params["shared_attn"]
        full = (cache["k"], cache["v"])

        def superblock(x, xs):
            pls, conv, st, i = xs

            def mamba_body(x, ys):
                pl, conv_i, st_i = ys
                h, new = ssm_mod.mamba2_decode(
                    pl["ssm"], apply_norm(cfg.norm, x, pl["norm1"]),
                    {"conv": conv_i, "ssm": st_i}, cfg)
                return x + h, (new["conv"], new["ssm"])
            x, (conv, st) = jax.lax.scan(mamba_body, x, (pls, conv, st))
            mine = [jax.lax.dynamic_index_in_dim(c, i, keepdims=False)
                    for c in full]
            h, *new = attn.gqa_decode_step(
                shared["attn"], apply_norm(cfg.norm, x, shared["norm1"]),
                *mine, cos, sin, cfg=cfg, pos=pos)
            x = x + h
            h = mlp_apply(shared["mlp"],
                          apply_norm(cfg.norm, x, shared["norm2"]),
                          cfg.activation)
            return x + h, (conv, st, new)

        pls = jax.tree_util.tree_map(
            lambda t: t.reshape(nsb, k, *t.shape[1:]), params["layers"])
        conv = cache["conv"].reshape(nsb, k, *cache["conv"].shape[1:])
        st = cache["ssm"].reshape(nsb, k, *cache["ssm"].shape[1:])
        x, (conv, st, new) = jax.lax.scan(
            superblock, x, (pls, conv, st, jnp.arange(nsb)))
        return x, dict(cache, conv=conv.reshape(cache["conv"].shape),
                       ssm=st.reshape(cache["ssm"].shape),
                       **_write_at(("k", "v"), full, new, pos))

    windows = _window_schedule(cfg)
    is_encdec, mla = cfg.family == "encdec", cfg.mla is not None
    names = ("ckv", "krope") if mla else ("k", "v")
    full = tuple(cache[n] for n in names)

    def body(x, xs):
        if is_encdec:
            pl, li, window, xk, xv = xs
        else:
            pl, li, window = xs
        h_in = apply_norm(cfg.norm, x, pl["norm1"])
        mine = [jax.lax.dynamic_index_in_dim(c, li, keepdims=False)
                for c in full]
        if mla:
            h, *upd = attn.mla_decode(pl["attn"], h_in, *mine, cos, sin,
                                      cfg=cfg, pos=pos)
            with jax.named_scope("kv_cache"):   # the position axis: -2
                new = tuple(jax.lax.dynamic_slice_in_dim(c, pos, 1,
                                                         c.ndim - 2)
                            for c in upd)
        else:
            h, *new = attn.gqa_decode_step(pl["attn"], h_in, *mine, cos,
                                           sin, cfg=cfg, pos=pos,
                                           window=window)
        x = x + h
        if is_encdec:
            x = x + attn.cross_decode(
                pl["xattn"], apply_norm(cfg.norm, x, pl["norm_x"]), xk, xv,
                cfg=cfg)
        h_in = apply_norm(cfg.norm, x, pl["norm2"])
        if "moe" in pl:
            h, _ = moe_mod.moe_apply(pl["moe"], h_in, cfg)
        else:
            h = mlp_apply(pl["mlp"], h_in, cfg.activation)
        return x + h, new

    news = []
    for stack, first, n in _stacks(cfg, params):
        w = windows if n == cfg.n_layers else windows[first:first + n]
        xs = (stack, jnp.arange(first, first + n), w)
        if is_encdec:
            xs += (cache["xk"], cache["xv"])
        x, new = jax.lax.scan(body, x, xs)
        news.append(new)
    return x, dict(cache, **_write_at(names, full, _concat_layers(news), pos))


@jax.named_scope("kv_cache")
def _write_at(names, full, news, pos):
    """{name: the cache ``full[i]`` with every layer's new entry
    ``news[i]`` written at ``pos`` on its axis -2}."""
    return {name: jax.lax.dynamic_update_slice(
                c, new, (0,) * (c.ndim - 2) + (pos, 0))
            for name, c, new in zip(names, full, news)}
