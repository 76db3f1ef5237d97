"""Serving cells of a model that no one chip holds: DeepSeek-V2 through
``repro.launch.serve.serve(..., mesh=...)`` on the host's chips, timed
from outside the program as ``drivers/serve.py`` times one chip's cells.

The configuration file names the mesh (``data`` x ``model``); the weights
are made by ``chipbench.weights_mla`` directly in the shardings the
program's rules give them, so no chip ever holds the whole model, and
``serve()`` places the cache and each batch's rows by the same rules.  The
run's device memory is that of the fullest chip.  The check compares a
seeded sample of the served requests with ``reference/deepseek_v2.py``
(one device, float32, each layer regenerated from the seed) by the same
``mean_gap`` as the one-chip cells.
"""
from __future__ import annotations

import shutil
import time

import jax
import numpy as np

from chipbench import chip, trace as tr_mod, traffic, weights_mla
from chipbench.bench import ROOT, Cell, log
from chipbench.check import verdict
from chipbench.drivers.serve import _reduce, _serve
from chipbench.reference.deepseek_v2 import served_logits
from chipbench.reference.serve import gaps


def mesh_for(spec: dict):
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(**spec["mesh"])


def run(cell: Cell) -> dict:
    spec, tr = cell.spec, cell.traffic
    m = weights_mla.dims(spec)
    cfg = weights_mla.model_config(spec)
    mesh = mesh_for(spec)
    devices = list(mesh.devices.flat)
    B, N = tr["batch"], tr["max_new"]

    def serve(prompts, emit):
        return _serve()(cfg, params, prompts, batch=B, max_new=N, emit=emit,
                        mesh=mesh)

    with jax.profiler.TraceAnnotation("chipbench/setup"):
        params = weights_mla.program_params(spec, cfg, cell.seed, mesh)
        log(f"[setup] weights made on {len(devices)} devices at "
            f"{cell.since_start():.2f} s")
        # as drivers/serve.py: one batch of its own prompts through serve()
        # compiles (or loads) every program and times a batch
        warm = traffic.prompts(tr, m["V"], cell.seed, traffic.WARMUP, 1)
        for _ in range(2):
            with chip.CompileTimer() as ct:
                t = time.perf_counter()
                r = serve(warm, lambda _: None)
                batch_s = time.perf_counter() - t
            if not ct.cache_misses:
                break
        for name, mem in r.memory.items():
            log(f"[memory] {name}: program bytes a device "
                f"{chip.program_bytes(mem)} (arguments "
                f"{mem.argument_size_in_bytes}, outputs "
                f"{mem.output_size_in_bytes}, temporaries "
                f"{mem.temp_size_in_bytes}, aliased "
                f"{mem.alias_size_in_bytes})")
        program_peak = max(chip.program_bytes(mem)
                           for mem in r.memory.values())
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
        r = serve(prompts, emit)
    tokens = r.tokens
    starts = [t0] + done[:-1]
    latency = np.repeat(np.subtract(done, starts), B)[:len(prompts)]
    window_s = done[-1] - t0
    log(f"[window] {len(prompts)} requests in {window_s:.3f} s; serve() "
        f"compile {window_ct.seconds:.3f} s, {window_ct.cache_misses} "
        f"programs missed the persistent cache; batches take "
        f"{np.subtract(done, starts).round(3).tolist()[:12]} s")

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    log(f"[memory] peak bytes in use by device {peaks}")
    mem_peak = max([program_peak] + peaks)
    del r, params
    out = {"e2e": {"serve_tok_s": len(prompts) * N / window_s,
                   "req_p95_s": float(np.percentile(latency, 95)),
                   "setup_s": setup_s},
           "attempted": len(prompts),
           "failed": int(len(prompts) - len(tokens)),
           "memory_peak_bytes": mem_peak}

    ctx = {"kind": "serve", "compile_s": window_ct.seconds}
    if cell.trace:
        t = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out.update(_reduce(t))
        ctx.update(m=m, traffic=tr, peak=cell.peak, trace=t,
                   span=out.pop("span"), busy_s=out["busy_s"],
                   window_s=out["window_s"], chips=len(devices), mla=True)
    out["ctx"] = ctx

    mean_gap = check_gap(spec, cell.seed, tr, prompts, tokens)
    ok, checks = verdict({"mean_gap": mean_gap}, cell.limits)
    out["correct"] = ok and out["failed"] == 0
    out["checks"] = checks
    return out


def pick(seed: int, tr: dict, n: int) -> np.ndarray:
    """The requests the reference checks, drawn from the seed."""
    rng = np.random.default_rng([seed, 4])
    return np.sort(rng.choice(n, tr["check_requests"], replace=False))


def check_gap(spec, seed, tr, prompts, tokens) -> float:
    """Mean over the sampled requests' served tokens of how far each lies
    below the reference's best logit."""
    chosen = pick(seed, tr, len(prompts))
    served = tokens[chosen]
    t = time.perf_counter()
    ref = served_logits(spec, seed, np.stack(prompts)[chosen], served)
    g = gaps(ref, served)
    log(f"[check] {g.size} served tokens: {int((g > 0).sum())} are not the "
        f"reference's first choice; widest gap {float(g.max())!r}; reference "
        f"{time.perf_counter() - t:.1f} s")
    return float(np.mean(g))
