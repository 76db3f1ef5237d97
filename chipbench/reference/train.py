"""The reference over a training job's first steps: the same loss as the
program declares (next-token cross-entropy, a z-loss on the log partition
function, and for experts the load-balance and router z terms averaged
over layers), its gradient by ``jax.grad``, clipping by the global norm
and AdamW with bias correction, all in float32 at ``HIGHEST`` precision
from the seed's weights.  Each layer is rematerialised in the backward
pass, and the loss is taken one sequence at a time, so that it fits on
one chip beside nothing else.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import traffic, weights
from chipbench.check import flatten, leaf_norms, norms
from chipbench.reference import model as R
from chipbench.spec import dims

F32 = jnp.float32


def loss(params, tokens, labels, m, coef, fp8=False):
    L = m["L"]
    h = params["embed"][tokens]
    lb = rz = 0.0
    for i in range(L):
        w = {n: params[n][i] for n in params
             if n not in ("embed", "final_norm", "lm_head")}
        h, lb_i, rz_i = jax.checkpoint(
            lambda w, h: R.layer(w, h, m, fp8))(w, h)
        lb, rz = lb + lb_i, rz + rz_i

    @jax.checkpoint
    def per_sequence(hl):
        x, y = hl
        lg = R.logits(params, x, m, fp8)
        logz = jax.nn.logsumexp(lg, -1)
        nll = logz - jnp.take_along_axis(lg, y[:, None], -1)[:, 0]
        return jnp.sum(nll), jnp.sum(logz * logz)
    nll, z2 = jax.lax.map(per_sequence, (h, labels))
    n = tokens.size
    total = jnp.sum(nll) / n + coef["z_loss"] * jnp.sum(z2) / n
    if m["E"]:
        total = total + coef["moe_aux"] * lb / L + coef["moe_router_z"] * rz / L
    return total


def lr_at(step: int, hp: dict) -> float:
    """Warm-up then cosine decay, at the zero-based step index."""
    if step < hp["warmup"]:
        return hp["lr"] * step / max(hp["warmup"], 1)
    frac = min(max((step - hp["warmup"]) / max(hp["total_steps"] - hp["warmup"],
                                                1), 0.0), 1.0)
    return 0.5 * hp["lr"] * (1 + math.cos(math.pi * frac))


@functools.partial(jax.jit, static_argnames=("m", "coef", "hp", "fp8"),
                   donate_argnums=(0, 1, 2))
def _step(params, mom, vel, count, tokens, labels, lr, m, coef, hp, fp8):
    m, coef, hp = dict(m), dict(coef), dict(hp)
    val, g = jax.value_and_grad(loss)(params, tokens, labels, m, coef, fp8)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, hp["grad_clip"]
                                  / jnp.maximum(gn, 1e-9)), g)
    count = count + 1
    b1, b2 = hp["b1"], hp["b2"]
    mom = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, mom, g)
    vel = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, vel, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + hp["eps"])
                                  + hp["weight_decay"] * p), params, mom, vel)
    return params, mom, vel, val, gn, norms(g)


def first_steps(spec: dict, seed: int, tr: dict, n_steps: int,
                fp8: bool = False, rows=None) -> dict:
    """Losses, global gradient norms, per-leaf norms of the first clipped
    gradient (``g1``) and of the change over ``n_steps`` (``delta``).
    ``rows`` keeps only that many rows of each batch (a fault to plant)."""
    m = dims(spec)
    mk = tuple(sorted(m.items()))
    coef = tuple(sorted(tr["loss"].items()))
    hp = tuple(sorted(tr["optimizer"].items()))
    with jax.default_matmul_precision("highest"):
        params = {n: x.astype(F32) for n, x in
                  weights.make_all(spec, seed).items()}
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, gnorms = [], []
        for i in range(n_steps):
            b = {k: v[:rows] for k, v in
                 traffic.train_batch(tr, m["V"], seed, i).items()}
            params, mom, vel, val, gn, g = _step(
                params, mom, vel, jnp.float32(i), jnp.asarray(b["tokens"]),
                jnp.asarray(b["labels"]),
                jnp.float32(lr_at(i, tr["optimizer"])),
                mk, coef, hp, fp8)
            losses.append(float(val))
            gnorms.append(float(gn))
            if i == 0:
                g1 = flatten(g)
        del mom, vel
        p0 = weights.make_all(spec, seed)
        delta = leaf_norms({n: params[n] - p0[n].astype(F32) for n in params})
    return {"losses": losses, "grad_norms": gnorms, "g1": g1, "delta": delta}
