"""Serving steps: batched prefill and single-token decode with a sharded
KV / state cache.  ``serve_step`` for the dry-run decode shapes = one
decode_forward call (one new token against a seq_len cache).

Batch sizing and admission semantics live in ``repro.serve.policy`` —
the same ``BatchingPolicy`` dataclasses drive this real JAX path (see
``examples/serve_batch.py``) and the trace-driven simulator
(``repro.sim.serving``), so measured and modeled serving agree on what
"static" / "dynamic" / "continuous" batching means.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.config import ModelConfig
from repro.models import transformer as T


def make_prefill_step(cfg: ModelConfig, max_seq: int):
    def prefill_step(params, batch):
        return T.prefill_forward(cfg, params, batch, max_seq=max_seq)
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, greedy: bool = True):
    def decode_step(params, cache, tokens, pos):
        logits, cache = T.decode_forward(cfg, params, cache, tokens, pos)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache
    return decode_step
