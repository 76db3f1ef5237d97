#!/usr/bin/env python3
"""Chip smoke test: the repository's main path on a TPU, in one process.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # granite EP serving on four chips

One chip, in order:

  device   JAX must hold TPUs; on any other platform the script exits
           non-zero before a phase runs.
  serve    granite_moe_1b_a400m at every published width and all 24
           layers, bf16 weights from --seed, through the serve launcher's
           own loop (``repro.launch.serve.serve``): 8 requests of 2048
           prompt tokens, batch 8, 64 new tokens.
  train    3 steps of ``make_train_step`` on granite at full width with
           the depth cut to 8 of 24 layers, B=4, S=2048, params and
           optimizer state donated.
  kernels  matmul, flash_attention and mamba_scan compiled for the chip
           (never interpreted) in bf16 at granite widths, mamba_scan at
           d_inner 8192, each against ``kernels/ref.py``.

``--four-chips`` runs only granite serving on a data=1, model=4 mesh
(8 of the 32 experts on each chip, through the EP ``shard_map``) and the
same seed's serving on device 0 alone, and compares the two.

Every phase prints one line with its compile seconds, its check against
the stated tolerance, the device bytes of each program it compiled (from
``memory_analysis()``) and the device's ``bytes_in_use`` at its end, next
to the process's ``peak_bytes_in_use`` so far.  A failed
phase exits non-zero; nothing is caught on the way.  On success the last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.  Run it from a checkout of the repository: it needs ``src/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.config import ShapeConfig  # noqa: E402
from repro.dist import context as dist_ctx  # noqa: E402
from repro.dist.sharding import rules_for, set_active_rules  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.chip import (CompileTimer, program_bytes,  # noqa: E402
                               require_tpu, use_compile_cache)
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import model_inputs, serve  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve import make_prefill_step  # noqa: E402
from repro.train import (TrainConfig, init_train_state,  # noqa: E402
                         make_train_step)

ARCH = "granite_moe_1b_a400m"
REQUESTS, BATCH, PROMPT_LEN, MAX_NEW = 8, 8, 2048, 64
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 4, 2048, 3

# Decode through the cache against a prefill over the same tokens.  Both
# carry bf16 activations (8 significant bits) through 24 layers, with
# different reduction orders: the decode reads the whole cache under a
# mask, the prefill runs the chunked online softmax, and GEMV and GEMM
# sum in different orders.  Rounding then leaves a relative RMS error of
# a few parts in a thousand per layer that adds up over depth, and a
# near-tie in the router can send a token to another expert in one path.
# A wrong cache position, a stale cache or a dropped layer moves the
# logits by the order of their own size.
LOGIT_REL_RMS_TOL = 0.08


def memory_line(device, programs: dict) -> str:
    """The phase's compiled programs, each with its device bytes
    (arguments + outputs + temporaries, a donated argument counted once)
    and its temporaries; then the device's ``bytes_in_use`` after the
    phase and its ``peak_bytes_in_use`` since the process started."""
    stats = device.memory_stats()
    progs = ", ".join(f"{name} {program_bytes(m)} (temporaries "
                      f"{m.temp_size_in_bytes})"
                      for name, m in programs.items())
    return (f"compiled bytes {progs}; after the phase bytes_in_use "
            f"{stats['bytes_in_use']}, process peak_bytes_in_use "
            f"{stats['peak_bytes_in_use']}")


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """Relative RMS error, max abs error, finiteness and argmax agreement
    of two (batch, vocab) logit arrays."""
    err = got - want
    return {
        "rel_rms": float(np.sqrt(np.mean(err ** 2) / np.mean(want ** 2))),
        "max_abs": float(np.max(np.abs(err))),
        "finite": bool(np.isfinite(got).all() and np.isfinite(want).all()),
        "argmax_agree": int(np.sum(got.argmax(-1) == want.argmax(-1))),
        "n": len(got),
    }


def agreement_ok(c: dict) -> bool:
    return c["finite"] and c["rel_rms"] <= LOGIT_REL_RMS_TOL


def fmt(c: dict) -> str:
    return (f"rel_rms {c['rel_rms']:.5f} <= {LOGIT_REL_RMS_TOL}, max_abs "
            f"{c['max_abs']:.4f}, argmax agree {c['argmax_agree']}/{c['n']},"
            f" finite {c['finite']}")


def prompts_for(cfg, seed: int, n: int, length: int) -> list:
    rng = np.random.default_rng(seed)
    return list(rng.integers(0, cfg.vocab, (n, length), dtype=np.int32))


def cache_logits(cfg, params, r) -> np.ndarray:
    """Logits of one more decode step through the served cache, fed the
    last generated token.  ``r`` must hold one batch."""
    assert r.cache["k"].shape[1] == len(r.tokens), "one batch only"
    logits, _ = jax.jit(functools.partial(T.decode_forward, cfg))(
        params, r.cache, jnp.asarray(r.tokens[:, -1:]),
        jnp.int32(r.next_pos))
    return np.asarray(logits[:, 0], np.float32)


def sequence_logits(cfg, params, prompts, tokens) -> np.ndarray:
    """Last-position logits of a prefill over prompt + generated tokens."""
    seq = np.concatenate([np.stack(prompts), tokens], axis=1)
    logits, _ = jax.jit(make_prefill_step(cfg, max_seq=seq.shape[1]))(
        params, model_inputs(cfg, jnp.asarray(seq)))
    return np.asarray(logits[:, -1], np.float32)


def serve_and_check(cfg, params, prompts, *, batch, max_new):
    """Serve, then check the decode through the cache against a prefill
    over the same tokens.  Returns (result, comparison, sequence
    logits)."""
    r = serve(cfg, params, prompts, batch=batch, max_new=max_new,
              emit=lambda _: None)
    want = sequence_logits(cfg, params, prompts, r.tokens)
    return r, compare(cache_logits(cfg, params, r), want), want


def phase_serve(seed: int, device) -> bool:
    cfg = get_config(ARCH)
    params, _ = T.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = prompts_for(cfg, seed, REQUESTS, PROMPT_LEN)
    r, c, _ = serve_and_check(cfg, params, prompts, batch=BATCH,
                              max_new=MAX_NEW)
    ok = agreement_ok(c)
    print(f"[serve] {ARCH} {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.moe.n_experts} experts top-{cfg.moe.top_k} (capacity "
          f"factor {cfg.moe.capacity_factor}: no token dropped), bf16; "
          f"{REQUESTS} requests x {PROMPT_LEN} prompt "
          f"tokens, batch {BATCH}, {MAX_NEW} new: compile "
          f"{r.compile_s:.1f} s, decode "
          f"{1e3 * r.decode_s / r.n_decode_steps:.3f} ms/step "
          f"({BATCH} tokens/step, {r.n_decode_steps} timed steps); "
          f"decode through the cache vs prefill: {fmt(c)}; "
          f"{memory_line(device, r.memory)} -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def phase_train(seed: int, device) -> bool:
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    params, opt, _, _ = init_train_state(cfg, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(cfg, TrainConfig(warmup=0,
                                                    total_steps=100)),
                   donate_argnums=(0, 1))
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab, (TRAIN_BATCH, TRAIN_SEQ + 1),
                        dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    with CompileTimer() as timer:
        step = step.lower(params, opt, batch, jnp.int32(0)).compile()
    mem = step.memory_analysis()
    losses, step_s = [], []
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        params, opt, metrics = step(params, opt, batch, jnp.int32(i))
        losses.append(float(metrics["loss"]))         # waits for the step
        step_s.append(time.perf_counter() - t0)
    ok = all(math.isfinite(v) for v in losses)
    steps_ms = [round(1e3 * s, 3) for s in step_s]
    print(f"[train] {ARCH} full width, depth cut to {TRAIN_LAYERS} of "
          f"{full.n_layers} layers (one chip holds bf16 params + fp32 "
          f"Adam state for {TRAIN_LAYERS}), B={TRAIN_BATCH} S={TRAIN_SEQ}, params and "
          f"optimizer state donated: compile {timer.seconds:.1f} s, "
          f"steps {steps_ms} ms; loss {losses} finite {ok}; "
          f"{memory_line(device, {'train step': mem})} -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


# Kernels against kernels/ref.py, which computes in f32 at "highest"
# precision and rounds to bf16.  An output is within bounds when
# |got - ref| <= KERNEL_RTOL * |ref| + atol: rtol 2^-7 allows one bf16 ulp
# (2^-8 to 2^-7 of the value) where two f32 results summed in other orders
# round apart, and atol allows for values near zero.  Each case also
# bounds the relative RMS error over its whole output.  Each bound sits a
# margin above the largest error measured:
#   matmul, mamba_scan: no output differs on a v5e; on the CPU's
#     interpreter, which sums in other orders, rel RMS 1.1e-5 and 1.2e-5
#     with every output within atol 2^-8.  Bound: rel RMS 1e-4, atol 2^-8.
#   flash_attention: rel RMS 2.1e-3, atol needed 1.9e-3 on a v5e: the
#     kernel's f32 dot of p and v runs at the MXU's default precision,
#     which rounds p to bf16.  Bound: rel RMS 4e-3, atol 2^-8.
# A mamba_scan that carried its state in bf16 gave rel RMS 2.9e-3 and
# put 34107 of 524288 outputs outside rtol 2^-7 + atol 2^-8 (CPU
# interpreter, d_inner 256, S 2048): it fails both bounds.
KERNEL_RTOL = 2.0 ** -7


def _kernel_cases(seed: int):
    """(name, kernel, args, reference, rel RMS bound, atol)."""
    cfg = get_config(ARCH)
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf = jnp.bfloat16

    def rnd(i, shape, dtype=bf):
        return jax.random.normal(k[i], shape, jnp.float32).astype(dtype)

    # matmul: one serve batch's prefill tokens x d_model -> d_ff_expert
    M, K, N = BATCH * PROMPT_LEN, cfg.d_model, cfg.moe.d_ff_expert
    a, b = rnd(0, (M, K)), rnd(1, (K, N))
    yield (f"matmul {M}x{K}x{N}", ops.matmul, (a, b), ref.matmul_ref(a, b),
           1e-4, 2.0 ** -8)
    # flash attention: granite's GQA heads and head_dim at the prompt length
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = rnd(2, (2, H, PROMPT_LEN, D))
    kk, v = rnd(3, (2, Hkv, PROMPT_LEN, D)), rnd(4, (2, Hkv, PROMPT_LEN, D))
    want = ref.flash_attention_ref(q, jnp.repeat(kk, H // Hkv, 1),
                                   jnp.repeat(v, H // Hkv, 1))
    yield (f"flash_attention B2 H{H}/Hkv{Hkv} S{PROMPT_LEN} D{D}",
           ops.flash_attention, (q, kk, v), want, 4e-3, 2.0 ** -8)
    # selective scan at d_inner 8192, N 16 (falcon-mamba's widths)
    d, n = 8192, 16
    x = rnd(5, (1, PROMPT_LEN, d))
    dt = jax.nn.softplus(rnd(6, (1, PROMPT_LEN, d), jnp.float32)).astype(bf)
    Bm, C = rnd(7, (1, PROMPT_LEN, n)), rnd(8, (1, PROMPT_LEN, n))
    A = -jnp.exp(0.3 * jax.random.normal(k[0], (d, n), jnp.float32))
    Dv = jnp.ones((d,), jnp.float32)
    yield (f"mamba_scan d_inner {d} N {n} S {PROMPT_LEN}", ops.mamba_scan,
           (x, dt, Bm, C, A, Dv), ref.mamba_scan_ref(x, dt, Bm, C, A, Dv),
           1e-4, 2.0 ** -8)


def kernel_error(got: np.ndarray, want: np.ndarray) -> dict:
    """Relative RMS error, the number of outputs that differ, the max
    abs error, and the smallest atol that passes at KERNEL_RTOL."""
    err = np.abs(got - want)
    return {
        "rel_rms": float(np.sqrt(np.mean(err ** 2) / np.mean(want ** 2))),
        "n_diff": int(np.count_nonzero(err)),
        "max_abs": float(err.max()),
        "atol_needed": max(0.0, float(np.max(err - KERNEL_RTOL
                                              * np.abs(want)))),
    }


def phase_kernels(seed: int, device) -> bool:
    all_ok = True
    with jax.default_matmul_precision("highest"):   # exact f32 references
        cases = list(_kernel_cases(seed))
    for name, kernel, args, want, rms_tol, atol in cases:
        with CompileTimer() as timer:
            compiled = jax.jit(kernel).lower(*args).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        compiled(*args).block_until_ready()                 # warm
        t0 = time.perf_counter()
        got = compiled(*args).block_until_ready()
        run_s = time.perf_counter() - t0
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        e = kernel_error(got, want)
        within = e["rel_rms"] <= rms_tol and e["atol_needed"] <= atol
        ok = mosaic and bool(np.isfinite(got).all()) and within
        all_ok &= ok
        print(f"[kernel] {name} bf16: compile {timer.seconds:.2f} s, "
              f"tpu_custom_call {mosaic}, run {1e3 * run_s:.3f} ms; "
              f"{e['n_diff']}/{got.size} outputs differ from the "
              f"reference, rel_rms {e['rel_rms']:.3g} <= "
              f"{rms_tol:.3g}, max abs err {e['max_abs']:.6g} (max "
              f"|ref| {np.abs(want).max():.6g}), atol needed at rtol "
              f"2^-7 {e['atol_needed']:.3g} <= {atol:.3g}: {within}; "
              f"{memory_line(device, {'kernel': compiled.memory_analysis()})}"
              f" -> {'ok' if ok else 'FAILED'}", flush=True)
    return all_ok


def param_bytes_by_device(params) -> dict:
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(out.items()))


def phase_four_chips(seed: int, devices) -> bool:
    """granite serving with the experts split over a model=4 mesh,
    against the same seed served on device 0 alone."""
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 chips, JAX holds "
                         f"{len(devices)}")
    cfg = get_config(ARCH)
    prompts = prompts_for(cfg, seed, REQUESTS, PROMPT_LEN)
    with jax.default_device(devices[0]):
        params, axes = T.init_params(cfg, jax.random.PRNGKey(seed))
        r1, c1, want1 = serve_and_check(cfg, params, prompts, batch=BATCH,
                                        max_new=MAX_NEW)
    one_bytes = param_bytes_by_device(params)
    tokens1 = r1.tokens
    print(f"[four-chips] one chip (device 0): decode through the cache "
          f"vs prefill: {fmt(c1)}; param bytes by device {one_bytes}; "
          f"{memory_line(devices[0], r1.memory)}", flush=True)
    del r1                                  # its cache, on device 0

    mesh = make_host_mesh(1, 4)
    shape = ShapeConfig("serve", seq_len=PROMPT_LEN + MAX_NEW,
                        global_batch=BATCH, kind="decode")
    rules = rules_for(cfg, shape, mesh)
    set_active_rules(rules)
    dist_ctx.set_mesh(mesh)
    try:
        params = jax.device_put(params, rules.tree_shardings(axes, params))
        split = param_bytes_by_device(params)
        r4, c4, _ = serve_and_check(cfg, params, prompts, batch=BATCH,
                                    max_new=MAX_NEW)
        # the same tokens through both: the four-chip prefill over the
        # one-chip run's prompt + continuation
        across = compare(sequence_logits(cfg, params, prompts, tokens1),
                         want1)
        same_tokens = int((r4.tokens == tokens1).all(axis=1).sum())
    finally:
        set_active_rules(None)
        dist_ctx.set_mesh(None)
    expert_leaf = params["layers"]["moe"]["up"]
    experts_per_chip = {s.device.id: s.data.shape[1]
                        for s in expert_leaf.addressable_shards}
    stats = {d.id: d.memory_stats() for d in devices}
    in_use = {i: m["bytes_in_use"] for i, m in stats.items()}
    peaks = {i: m["peak_bytes_in_use"] for i, m in stats.items()}
    per_device = ", ".join(f"{k} {program_bytes(m)}"
                           for k, m in r4.memory.items())
    ok = (agreement_ok(c1) and agreement_ok(c4) and agreement_ok(across)
          and set(experts_per_chip.values()) == {cfg.moe.n_experts // 4})
    print(f"[four-chips] data=1 model=4 mesh, EP shard_map: experts per "
          f"chip {experts_per_chip}, param bytes by device {split} (one "
          f"chip held {sum(one_bytes.values())}); compile "
          f"{r4.compile_s:.1f} s, decode "
          f"{1e3 * r4.decode_s / r4.n_decode_steps:.3f} ms/step; decode "
          f"through the cache vs prefill: {fmt(c4)}; four chips vs one on "
          f"the same tokens: {fmt(across)}; continuations equal to one "
          f"chip's {same_tokens}/{len(tokens1)}; compiled bytes on each "
          f"device {per_device}; bytes_in_use by device {in_use}, process "
          f"peak_bytes_in_use by device {peaks} -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-chip EP serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = require_tpu()
    dev = devices[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}; compile cache {use_compile_cache()}",
          flush=True)
    if args.four_chips:
        ok = phase_four_chips(args.seed, devices)
    else:
        ok = True
        for phase in (phase_serve, phase_train, phase_kernels):
            ok &= phase(args.seed, dev)
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
