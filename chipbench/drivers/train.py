"""Training cells: ``repro.train.make_train_step`` under ``jax.jit`` with
the parameters and optimizer state donated.

Set-up builds one object, the compiled step with its state, and drives it
through the job's first three steps on batches 0, 1 and 2, reading what
the check compares: each step's loss and global gradient norm, each
leaf's first gradient as the optimizer received it (its first moment
after one step, over 1 - beta1) and each leaf's change over the three
steps.  The window then runs the same object on further batches back to
back and ends in ``block_until_ready``.
"""
from __future__ import annotations

import shutil
import time

import jax
import jax.numpy as jnp

from chipbench import chip, counts, trace as tr_mod, traffic, weights
from chipbench.bench import ROOT, Cell, log
from chipbench.check import leaf_norms, moved, rel, verdict, worst_leaf
from chipbench.reference import train as ref_train
from chipbench.spec import dims, model_config

CHECKED_STEPS = 3


def _program():
    import repro.train as RT   # looked up per call: tests patch it
    from repro.optim import adamw_init
    return RT.make_train_step, RT.TrainConfig, adamw_init


def first_steps(cell: Cell):
    """Build the compiled step with its state and run the job's first
    steps through it.  Returns (step, params, opt, readings, memory,
    seconds of the fastest later step)."""
    spec, tr = cell.spec, cell.traffic
    cfg = model_config(spec)
    make_train_step, TrainConfig, adamw_init = _program()
    hp = tr["optimizer"]
    tc = TrainConfig(lr=hp["lr"], warmup=hp["warmup"],
                     total_steps=hp["total_steps"], grad_clip=hp["grad_clip"],
                     weight_decay=hp["weight_decay"])

    def named(tree):
        return weights.named(spec, cfg, tree)

    params = weights.program_params(spec, cfg, cell.seed)
    opt = jax.jit(adamw_init)(params)
    step = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    with chip.CompileTimer() as ct:
        step = step.lower(params, opt, batch(cell, 0), jnp.int32(0)).compile()
    mem = step.memory_analysis()
    log(f"[memory] train step: program bytes {chip.program_bytes(mem)} "
        f"(arguments {mem.argument_size_in_bytes}, outputs "
        f"{mem.output_size_in_bytes}, temporaries {mem.temp_size_in_bytes}"
        f", aliased {mem.alias_size_in_bytes}); compile {ct.seconds:.2f} s")
    losses, gnorms, step_s = [], [], []
    for i in range(CHECKED_STEPS):
        t = time.perf_counter()
        params, opt, met = step(params, opt, batch(cell, i), jnp.int32(i))
        losses.append(float(met["loss"]))
        gnorms.append(float(met["grad_norm"]))
        step_s.append(time.perf_counter() - t)
        if i == 0:   # before the next step donates the moments
            g1 = leaf_norms({n: x / (1 - hp["b1"]) for n, x in
                                    named(opt["m"]).items()})
    p0 = weights.program_params(spec, cfg, cell.seed)
    delta = leaf_norms({n: a.astype(jnp.float32) - b.astype(
        jnp.float32) for (n, a), b in zip(named(params).items(),
                                           named(p0).values())})
    del p0
    log(f"[setup] first steps {[round(s, 4) for s in step_s]} s")
    readings = {"losses": losses, "grad_norms": gnorms, "g1": g1,
                "delta": delta}
    return step, params, opt, readings, mem, min(step_s[1:])


def batch(cell: Cell, i: int) -> dict:
    return {k: jnp.asarray(v) for k, v in traffic.train_batch(
        cell.traffic, cell.spec["vocab_size"], cell.seed, i).items()}


def compare(prog: dict, ref: dict) -> dict:
    """The four numbers compared: each step's loss and global gradient
    norm (the worst of the steps, relative), the worst leaf of the first
    gradient, and the worst leaf of the change over the steps, leaving out
    leaves whose reference gradient is nought to rounding."""
    keep = moved(ref["g1"])
    return {
        "loss": max(rel(p, r) for p, r in zip(prog["losses"], ref["losses"])),
        "grad_norm": max(rel(p, r) for p, r in zip(prog["grad_norms"],
                                                   ref["grad_norms"])),
        "grad1_leaf": worst_leaf(prog["g1"], ref["g1"])[0],
        "change_leaf": worst_leaf(prog["delta"], ref["delta"], keep)[0]}


def run(cell: Cell) -> dict:
    tr = cell.traffic
    m = dims(cell.spec)
    with jax.profiler.TraceAnnotation("chipbench/setup"):
        step, params, opt, prog, mem, per_step = first_steps(cell)
        n_steps = max(CHECKED_STEPS, round(cell.seconds / per_step))
        if cell.trace:
            n_steps = min(n_steps, tr["trace_steps"])
        feed = [(batch(cell, i), jnp.int32(i)) for i in
                range(CHECKED_STEPS, CHECKED_STEPS + n_steps)]
        log(f"[setup] losses {prog['losses']}; window of {n_steps} steps")

    trace_dir = str(ROOT / ".chipbench" / "trace" / cell.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if cell.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = cell.since_start()
    with chip.CompileTimer() as window_ct:
        with jax.profiler.TraceAnnotation("chipbench/steps"):
            t0 = time.perf_counter()
            for i, (b, s) in enumerate(feed):
                with jax.profiler.TraceAnnotation("chipbench/step"):
                    params, opt, _ = step(params, opt, b, s)
            jax.block_until_ready((params, opt))
            window_s = time.perf_counter() - t0
    if cell.trace:
        jax.profiler.stop_trace()
    log(f"[window] {n_steps} steps in {window_s:.3f} s from {setup_s:.3f} s; "
        f"{window_ct.cache_misses} programs missed the persistent cache")
    mem_peak = max(chip.program_bytes(mem),
                   (jax.devices()[0].memory_stats() or {}).get(
                       "peak_bytes_in_use", 0))
    del params, opt, step, feed
    out = {"e2e": {"train_tok_s": n_steps * tr["batch"] * tr["seq_len"]
                   / window_s, "setup_s": setup_s},
           "attempted": n_steps, "failed": 0, "memory_peak_bytes": mem_peak,
           "ctx": {"kind": "train"}}
    if cell.trace:
        t = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        (_, lo, dur), = [s for s in t.spans if s[0] == "chipbench/steps"]
        busy_s, breakdown = tr_mod.window(t, lo, lo + dur)
        out.update(busy_s=busy_s, window_s=dur, breakdown=breakdown)
        out["ctx"] = {"kind": "train", "window_s": dur,
                      "busy_s": out["busy_s"], "peak": cell.peak,
                      "flops": n_steps * tr["batch"] * tr["seq_len"]
                      * counts.train_flops_per_token(m, tr["seq_len"])}

    t = time.perf_counter()
    ref = ref_train.first_steps(cell.spec, cell.seed, tr, CHECKED_STEPS)
    log(f"[check] reference {time.perf_counter() - t:.1f} s; program "
        f"losses {prog['losses']} grad norms {prog['grad_norms']}; "
        f"reference losses {ref['losses']} grad norms "
        f"{ref['grad_norms']}; worst leaves "
        f"{worst_leaf(prog['g1'], ref['g1'])[1]}, "
        f"{worst_leaf(prog['delta'], ref['delta'], moved(ref['g1']))[1]}; "
        f"{len(ref['g1']) - len(moved(ref['g1']))} leaves left out of the "
        f"change")
    out["correct"], out["checks"] = verdict(compare(prog, ref), cell.limits)
    return out
