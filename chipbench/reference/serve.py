"""The reference over served requests: for each prompt with its served
tokens, the float32 logits at every position that produced a served token,
computed layer by layer with each layer's weights regenerated from the
seed, so that the whole model never has to sit on the device in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import model as R
from chipbench.spec import dims


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(w, h, m, fp8):
    return R.layer(w, h, dict(m), fp8)[0]


@functools.partial(jax.jit, static_argnames=("m", "fp8", "first"))
def _head(g, h, m, fp8, first):
    return R.logits(g, h[:, first:], dict(m), fp8)


def served_logits(spec: dict, seed: int, prompts: np.ndarray,
                  served: np.ndarray, fp8: bool = False):
    """prompts (n, P), served (n, N) -> logits (n, N, V): position P-1+j
    of prompt + served[:-1] predicts served[:, j].  Runs in blocks of
    ``block`` sequences so the float32 activations fit beside nothing
    else."""
    with jax.default_matmul_precision("highest"):
        m = tuple(sorted(dims(spec).items()))
        seqs = np.concatenate([prompts, served[:, :-1]], 1)
        P = prompts.shape[1]
        g = weights.globals_f32(spec, seed)
        key = weights.base_key(seed)
        layer_w = weights.layer_f32(spec)
        h = g["embed"][jnp.asarray(seqs)]
        for i in range(dims(spec)["L"]):
            h = _layer(layer_w(key, i), h, m=m, fp8=fp8)
        return _head(g, h, m=m, fp8=fp8, first=P - 1)


def gaps(ref_logits, tokens) -> np.ndarray:
    """How far below the reference's best logit each given token's logit
    lies, (n, N)."""
    ref_logits = jnp.asarray(ref_logits)
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              -1)[..., 0]
    return np.asarray(best - got)
