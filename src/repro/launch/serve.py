"""Serving launcher: static batches of greedy generation requests.

A request queue feeds fixed-size batches.  Each batch is prefilled in
one jitted call (``make_prefill_step``), then decoded one token per
jitted step (``make_decode_step``) against its KV cache.  A short last
batch is padded with copies of its last prompt, so prefill and decode
each compile once.

  python -m repro.launch.serve --arch granite_moe_1b_a400m
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b --smoke

The full config runs by default and needs a TPU: without one the
launcher exits instead of going on on the CPU.  ``--smoke`` selects the
reduced preset of the same family, which runs on any device.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import time
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import get_config, get_smoke_config
from repro.core.config import ModelConfig, ShapeConfig
from repro.dist import context as dist_ctx
from repro.dist import sharding as shd
from repro.launch.chip import (CompileTimer, program_bytes, require_tpu,
                               use_compile_cache)
from repro.models import transformer as T
from repro.serve import make_decode_step, make_prefill_step


@dataclass
class ServeResult:
    tokens: np.ndarray      # (n_requests, max_new) greedy continuations
    cache: Any              # last batch's cache after its last decode step
    next_pos: int           # cache position of the last generated token
    compile_s: float        # tracing + lowering + compiling, all steps
    decode_s: float         # timed decode steps, ends in block_until_ready
    n_decode_steps: int     # decode steps inside ``decode_s``
    memory: dict            # "prefill"/"decode": compiled memory_analysis()


def model_inputs(cfg: ModelConfig, tokens) -> dict:
    """The prefill batch for ``tokens``; enc-dec frames and VLM patches
    are constant stand-ins for the (stub) encoder and vision tower."""
    B = tokens.shape[0]
    batch = {"tokens": tokens}
    if cfg.family == "encdec":
        batch["frames"] = jnp.full((B, cfg.encoder.n_ctx, cfg.d_model), .1,
                                   jnp.float32)
    if cfg.family == "vlm":
        batch["patches"] = jnp.full((B, cfg.n_patches, cfg.d_model), .1,
                                    jnp.float32)
    return batch


@contextlib.contextmanager
def _installed(mesh, rules):
    """``mesh`` and its sharding ``rules`` active for the model code."""
    before = dist_ctx.get_mesh(), shd.active_rules()
    dist_ctx.set_mesh(mesh)
    shd.set_active_rules(rules)
    try:
        yield
    finally:
        dist_ctx.set_mesh(before[0])
        shd.set_active_rules(before[1])


def _on_mesh(cfg: ModelConfig, mesh, batch: int, max_seq: int):
    """The sharding rules of this serving shape on ``mesh``, and the
    shardings of the params, of the cache and of a batch's rows, from
    their ``Leaf`` axes by ``rules_for``."""
    rules = shd.rules_for(cfg, ShapeConfig("serve", seq_len=max_seq,
                                           global_batch=batch,
                                           kind="decode"), mesh)
    axes = {}

    def shapes():
        params, axes["params"] = T.init_params(cfg, jax.random.key(0))
        cache, axes["cache"] = T.init_cache(cfg, batch, max_seq)
        return params, cache
    params, cache = jax.eval_shape(shapes)
    rows = NamedSharding(mesh, rules.spec_for(("batch",), (batch,)))
    return (rules, rules.tree_shardings(axes["params"], params),
            rules.tree_shardings(axes["cache"], cache), rows)


def serve(cfg: ModelConfig, params, prompts: Sequence[np.ndarray], *,
          batch: int, max_new: int, emit=print, mesh=None) -> ServeResult:
    """Generate ``max_new`` greedy tokens for each prompt, in static
    batches of ``batch``.  All prompts have one length.

    The first decode step of each batch runs outside the timed window,
    since on the first batch it compiles; ``decode_s`` covers the other
    ``max_new - 2`` steps of every batch.

    Each batch's phases are profiler spans that carry the batch's index
    as ``batch``: ``serve/admit`` (pad, stack, move to the device),
    ``serve/prefill`` (dispatch prefill and the first token's argmax),
    ``serve/decode`` (the decode steps, up to the last token's being
    ready) and ``serve/collect`` (the tokens to the host).  On the first
    batch, prefill and decode also trace and compile their steps.
    ``emit`` runs between batches, outside the spans.

    With a ``mesh``, the mesh and its ``rules_for`` rules are installed
    for the call; params, cache and each batch's rows are placed by their
    logical axes, and the MoE layers take their expert-parallel path over
    the mesh's ``model`` axis."""
    if max_new < 3:
        raise ValueError(f"max_new={max_new}: needs 3 or more, since two "
                         "tokens come from untimed steps")
    prompt_len = len(prompts[0])
    pos0 = prompt_len + (cfg.n_patches if cfg.family == "vlm" else 0)
    step = make_prefill_step(cfg, max_seq=pos0 + max_new)
    if mesh is None:
        prefill = jax.jit(step)
        decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
        place, installed = (lambda x: x), contextlib.nullcontext()
    else:
        rules, params_sh, cache_sh, rows_sh = _on_mesh(cfg, mesh, batch,
                                                       pos0 + max_new)
        one = NamedSharding(mesh, PartitionSpec())
        prefill = jax.jit(step, in_shardings=(params_sh, rows_sh),
                          out_shardings=(one, cache_sh))
        decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,),
                         in_shardings=(params_sh, cache_sh, rows_sh, one),
                         out_shardings=(rows_sh, cache_sh))
        params = jax.device_put(params, params_sh)
        place = functools.partial(jax.device_put, device=rows_sh)
        installed = _installed(mesh, rules)
    queue = list(prompts)
    outs = []
    decode_s = 0.0
    with installed:
        with CompileTimer() as timer:
            while queue:
                b = len(outs)
                with TraceAnnotation("serve/admit", batch=b):
                    rows = queue[:batch]
                    queue = queue[batch:]
                    padded = rows + [rows[-1]] * (batch - len(rows))
                    inputs = place(model_inputs(
                        cfg, jnp.asarray(np.stack(padded))))
                with TraceAnnotation("serve/prefill", batch=b):
                    logits, cache = prefill(params, inputs)
                    tok = place(jnp.argmax(logits[:, -1], -1).astype(
                        jnp.int32)[:, None])
                    toks = [tok]
                with TraceAnnotation("serve/decode", batch=b):
                    tok, cache = decode(params, cache, tok, jnp.int32(pos0))
                    toks.append(tok)
                    tok.block_until_ready()
                    t0 = time.perf_counter()
                    for i in range(2, max_new):
                        tok, cache = decode(params, cache, tok,
                                            jnp.int32(pos0 + i - 1))
                        toks.append(tok)
                    tok.block_until_ready()
                    decode_s += time.perf_counter() - t0
                with TraceAnnotation("serve/collect", batch=b):
                    out = np.asarray(jnp.concatenate(toks, 1))[:len(rows)]
                    outs.append(out)
                emit(f"[batch] finished {len(rows)} requests "
                     f"({sum(len(o) for o in outs)}/{len(prompts)}); sample "
                     f"continuation: {out[0][:8]}")
        # lowering again at the same shapes reuses the executables compiled
        # above: this reads their memory, it compiles nothing
        lowered = {"prefill": prefill.lower(params, inputs),
                   "decode": decode.lower(params, cache, tok,
                                          jnp.int32(pos0))}
        memory = {k: low.compile().memory_analysis()
                  for k, low in lowered.items()}
    n_batches = len(outs)
    return ServeResult(tokens=np.concatenate(outs), cache=cache,
                       next_pos=pos0 + max_new - 1,
                       compile_s=timer.seconds, decode_s=decode_s,
                       n_decode_steps=n_batches * (max_new - 2),
                       memory=memory)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_moe_1b_a400m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced preset of the same family")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=2048)
    ap.add_argument("--max-new", type=int, default=64)
    args = ap.parse_args()

    if not args.smoke:
        require_tpu()
    use_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = list(rng.integers(0, cfg.vocab, (args.requests,
                                               args.prompt_len),
                                dtype=np.int32))
    t0 = time.perf_counter()
    r = serve(cfg, params, prompts, batch=args.batch, max_new=args.max_new)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"served {len(r.tokens)} requests in {dt:.2f}s on "
          f"{dev.platform} {dev.device_kind} x{len(jax.devices())}: "
          f"compile {r.compile_s:.1f}s, decode "
          f"{1e3 * r.decode_s / r.n_decode_steps:.2f} ms/step "
          f"(batch {args.batch}); compiled bytes prefill "
          f"{program_bytes(r.memory['prefill'])}, decode "
          f"{program_bytes(r.memory['decode'])}")


if __name__ == "__main__":
    main()
