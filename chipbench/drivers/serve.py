"""Serving cells: ``repro.launch.serve.serve`` over static batches, timed
from outside the program.

Closed loop with one client per batch slot: the queue holds
``n_batches`` static batches, and each request is timed from the start of
its batch (the ``serve()`` call for the first, the previous batch's
completion for the others) to its batch's completion on the host, which
``serve()`` reports through ``emit`` once the batch's tokens are on the
host.  ``serve()`` builds its ``jax.jit`` closures anew on every call, so
the window re-traces both steps and loads them from the persistent cache:
that cost is the program's, stays in the window, and is reported as
``serve.compile_s``.
"""
from __future__ import annotations

import shutil
import time

import jax
import numpy as np

from chipbench import chip, trace as tr_mod, traffic, weights
from chipbench.bench import ROOT, Cell, log
from chipbench.check import verdict
from chipbench.reference.serve import gaps, served_logits
from chipbench.spec import dims, model_config


def _serve():
    from repro.launch import serve as S   # looked up per call: tests patch it
    return S.serve


def run(cell: Cell) -> dict:
    spec, tr = cell.spec, cell.traffic
    m = dims(spec)
    cfg = model_config(spec)
    B, P, N = tr["batch"], tr["prompt_len"], tr["max_new"]
    with jax.profiler.TraceAnnotation("chipbench/setup"):
        params = weights.program_params(spec, cfg, cell.seed)
        jax.block_until_ready(params)
        log(f"[setup] weights made on the device at {cell.since_start():.2f} s")
        # one batch of its own prompts through serve() itself, so that every
        # program serve() runs is compiled (or loaded) and has run once; its
        # time says how many batches fill the window.  Where it compiled,
        # the device ran beside the compiler, so it is run again and timed.
        warm = traffic.prompts(tr, m["V"], cell.seed, traffic.WARMUP, 1)
        for _ in range(2):
            with chip.CompileTimer() as ct:
                t = time.perf_counter()
                r = _serve()(cfg, params, warm, batch=B, max_new=N,
                             emit=lambda _: None)
                batch_s = time.perf_counter() - t
            if not ct.cache_misses:
                break
        for name, mem in r.memory.items():
            log(f"[memory] {name}: program bytes {chip.program_bytes(mem)} "
                f"(arguments {mem.argument_size_in_bytes}, outputs "
                f"{mem.output_size_in_bytes}, temporaries "
                f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes})")
        program_peak = max(chip.program_bytes(mem) for mem in r.memory.values())
        del r
        n_batches = max(2, round(cell.seconds / batch_s))
        prompts = traffic.prompts(tr, m["V"], cell.seed, traffic.WINDOW,
                                  n_batches)
        log(f"[setup] warm-up batch {batch_s:.3f} s ({ct.seconds:.3f} s of it "
            f"compiling or loading); window of {n_batches} batches of {B}")

    trace_dir = str(ROOT / ".chipbench" / "trace" / cell.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    done, spans = [], []

    def emit(_msg):
        done.append(time.perf_counter())
        if spans:
            spans.pop().__exit__(None, None, None)
        if cell.trace and len(done) == 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        if cell.trace and len(done) == 2:
            jax.profiler.stop_trace()
        if len(done) < n_batches:
            spans.append(jax.profiler.TraceAnnotation("chipbench/batch"))
            spans[-1].__enter__()

    setup_s = cell.since_start()
    log(f"[window] starts at {setup_s:.3f} s")
    with chip.CompileTimer() as window_ct:
        t0 = time.perf_counter()
        spans.append(jax.profiler.TraceAnnotation("chipbench/batch"))
        spans[-1].__enter__()
        r = _serve()(cfg, params, prompts, batch=B, max_new=N, emit=emit)
    tokens = r.tokens
    starts = [t0] + done[:-1]
    latency = np.repeat(np.subtract(done, starts), B)[:len(prompts)]
    window_s = done[-1] - t0
    log(f"[window] {len(prompts)} requests in {window_s:.3f} s; serve() "
        f"compile {window_ct.seconds:.3f} s, {window_ct.cache_misses} "
        f"programs missed the persistent cache; batches take "
        f"{np.subtract(done, starts).round(3).tolist()[:12]} s")

    mem_peak = max(program_peak,
                   (jax.devices()[0].memory_stats() or {}).get(
                       "peak_bytes_in_use", 0))
    del r, params
    out = {"e2e": {"serve_tok_s": len(prompts) * N / window_s,
                   "req_p95_s": float(np.percentile(latency, 95)),
                   "setup_s": setup_s},
           "attempted": len(prompts),
           "failed": int(len(prompts) - len(tokens)),
           "memory_peak_bytes": mem_peak}

    if cell.trace:
        t = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out.update(_reduce(t))
        out["ctx"] = {"kind": "serve", "m": m, "traffic": tr,
                      "peak": cell.peak, "trace": t, "span": out.pop("span"),
                      "busy_s": out["busy_s"], "window_s": out["window_s"],
                      "compile_s": window_ct.seconds}
    else:
        out["ctx"] = {"kind": "serve", "compile_s": window_ct.seconds}

    # the reference over a sample of the finished requests, drawn from the seed
    rng = np.random.default_rng([cell.seed, 4])
    pick = np.sort(rng.choice(len(prompts), tr["check_requests"],
                              replace=False))
    served = tokens[pick]
    t = time.perf_counter()
    ref = served_logits(spec, cell.seed, np.stack(prompts)[pick], served)
    g = gaps(ref, served)
    log(f"[check] {g.size} served tokens: {int((g > 0).sum())} are not the "
        f"reference's first choice; widest gap {float(g.max())!r}; reference "
        f"{time.perf_counter() - t:.1f} s")
    ok, checks = verdict({"mean_gap": float(np.mean(g))}, cell.limits)
    out["correct"] = ok and out["failed"] == 0
    out["checks"] = checks
    return out


def _reduce(t: tr_mod.Trace) -> dict:
    """Busy time and breakdown of the traced batch."""
    (_, lo, dur), = [s for s in t.spans if s[0] == "chipbench/batch"]
    busy_s, breakdown = tr_mod.window(t, lo, lo + dur)
    return {"busy_s": busy_s, "window_s": dur, "span": (lo, lo + dur),
            "breakdown": breakdown}
