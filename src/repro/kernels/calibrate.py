"""Measured-vs-modeled calibration against the real Pallas kernels.

Closes the loop the paper's gem5 integration stands for: time the actual
JAX/Pallas kernels in ``repro/kernels/`` (``nvdla_matmul``,
``flash_attention``, ``mamba_scan``) across a shape grid with
timeit-style best-of-k, then fit cost-backend parameters by least
squares (:func:`repro.sim.backends.fit_linear_cost`) and build a
measured :class:`repro.sim.backends.TableBackend`.

On a TPU the kernels compile to Mosaic.  On a CPU host the caller must
pass ``interpret=True``: the measured times are then Python-interpreter
magnitudes, wildly off the TPU roofline constants, so the uncalibrated
roofline error is enormous and the fitted error is small.  Every report
carries the JAX backend and the interpret mode it was measured with.

Used by ``tools/calibrate.py`` (CLI) and
``benchmarks/bench_calibration.py`` (the CI-gated artifact writer).
"""
from __future__ import annotations

import math
from time import perf_counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.sim import backends as sim_backends
from repro.sim import hw

BYTES = 4  # kernels are measured in fp32

# Shape grids.  Each kernel's shapes carry pairwise-distinct flop counts
# on purpose: the measured TableBackend keys its exact round-trip on
# (kind, flops), so two shapes with equal flops but different runtimes
# would make "reproduce your own samples" unsatisfiable.
# (M, N, K) matmul grid
MATMUL_GRID: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 128), (256, 128, 128), (256, 256, 128),
    (256, 256, 256), (512, 256, 256), (512, 512, 256))
# (B, H, Hkv, S, D) attention grid (GQA rows keep KV at Hkv heads)
ATTENTION_GRID: Tuple[Tuple[int, int, int, int, int], ...] = (
    (1, 2, 2, 128, 32), (2, 2, 2, 128, 32), (1, 4, 2, 128, 64),
    (1, 2, 1, 256, 64), (2, 4, 2, 256, 32))
# (b, S, d, N) selective-scan grid
MAMBA_GRID: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 32, 16, 8), (1, 64, 32, 8), (2, 64, 32, 16), (1, 128, 64, 16))

QUICK_GRIDS = {"matmul": MATMUL_GRID[:2], "attention": ATTENTION_GRID[:2],
               "mamba": MAMBA_GRID[:2]}
FULL_GRIDS = {"matmul": MATMUL_GRID, "attention": ATTENTION_GRID,
              "mamba": MAMBA_GRID}
KERNELS = tuple(FULL_GRIDS)


# ---------------------------------------------------------------------------
# analytic accounting: nominal (flops, bytes) per kernel invocation.
# Attention bytes charge KV at its native Hkv heads — the kernel indexes
# KV by group instead of materializing the broadcast, so measured and
# modeled traffic compare like with like.


def matmul_cost(M: int, N: int, K: int) -> Tuple[float, float]:
    return 2.0 * M * N * K, float(BYTES * (M * K + K * N + M * N))


def attention_cost(B: int, H: int, Hkv: int, S: int, D: int,
                   causal: bool = True) -> Tuple[float, float]:
    flops = 4.0 * B * H * S * S * D * (0.5 if causal else 1.0)
    bytes_ = BYTES * (2.0 * B * H * S * D + 2.0 * B * Hkv * S * D)
    return flops, bytes_


def mamba_cost(b: int, S: int, d: int, N: int) -> Tuple[float, float]:
    flops = 10.0 * b * S * d * N
    bytes_ = BYTES * (3.0 * b * S * d + 2.0 * b * S * N + d * N + d)
    return flops, bytes_


# ---------------------------------------------------------------------------
# measurement


def _best_of(fn, repeat: int) -> float:
    fn()                                    # warmup (jit/interpret trace)
    best = math.inf
    for _ in range(max(repeat, 1)):
        t0 = perf_counter()
        fn()
        best = min(best, perf_counter() - t0)
    return best


def _measure_kernel(kernel: str, shape: Sequence[int], repeat: int,
                    interpret: bool) -> Dict:
    import jax
    from repro.kernels import ops

    rng = np.random.default_rng(hash((kernel,) + tuple(shape)) % 2**32)

    def rand(*s):
        return jax.numpy.asarray(
            rng.standard_normal(s).astype(np.float32))

    if kernel == "matmul":
        M, N, K = shape
        a, b = rand(M, K), rand(K, N)
        flops, bytes_ = matmul_cost(M, N, K)
        fn = lambda: ops.matmul(  # noqa: E731
            a, b, interpret=interpret).block_until_ready()
    elif kernel == "attention":
        B, H, Hkv, S, D = shape
        q = rand(B, H, S, D)
        k, v = rand(B, Hkv, S, D), rand(B, Hkv, S, D)
        flops, bytes_ = attention_cost(B, H, Hkv, S, D)
        fn = lambda: ops.flash_attention(  # noqa: E731
            q, k, v, bq=64, bk=64, interpret=interpret).block_until_ready()
    elif kernel == "mamba":
        b, S, d, N = shape
        x, dt = rand(b, S, d), rand(b, S, d)
        Bm, C = rand(b, S, N), rand(b, S, N)
        A, D = -jax.numpy.abs(rand(d, N)), rand(d)
        flops, bytes_ = mamba_cost(b, S, d, N)
        fn = lambda: ops.mamba_scan(  # noqa: E731
            x, dt, Bm, C, A, D, interpret=interpret).block_until_ready()
    else:
        raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")
    return {"kernel": kernel, "kind": kernel, "shape": list(shape),
            "flops": flops, "bytes": bytes_,
            "measured_s": _best_of(fn, repeat)}


def measure(grid: str = "full", repeat: int = 3,
            kernels: Sequence[str] = KERNELS, *,
            interpret: bool = False) -> Tuple[List[Dict], Dict]:
    """Time the Pallas kernels over the named shape grid.

    Returns ``(records, meta)``: per-shape records with the analytic
    (flops, bytes) accounting and best-of-``repeat`` seconds, plus meta
    naming the JAX backend and interpret mode the samples came from."""
    import jax
    grids = QUICK_GRIDS if grid == "quick" else FULL_GRIDS
    records = [_measure_kernel(kernel, shape, repeat, interpret)
               for kernel in kernels for shape in grids[kernel]]
    return records, {"backend": jax.default_backend(),
                     "interpret": interpret,
                     "grid": grid, "repeat": repeat}


# ---------------------------------------------------------------------------
# fitting + error reporting


def roofline_pred(records: Sequence[Dict],
                  peak_flops: float = hw.PEAK_FLOPS,
                  hbm_bw: float = hw.HBM_BW) -> np.ndarray:
    """The uncalibrated roofline prediction at the canonical hardware
    constants: ``flops/peak + bytes/bw`` per record."""
    f = np.array([r["flops"] for r in records])
    b = np.array([r["bytes"] for r in records])
    return f / peak_flops + b / hbm_bw


def calibrate(records: Sequence[Dict]) -> Dict[str, Dict]:
    """Per-kernel least-squares fit + error summary.

    For each kernel: the fitted effective (peak, bandwidth, overhead)
    from :func:`repro.sim.backends.fit_linear_cost`, the fitted MAPE,
    the uncalibrated-roofline MAPE, and the measured-table round-trip
    error (0 by construction — asserted, not assumed)."""
    out: Dict[str, Dict] = {}
    for kernel in {r["kernel"] for r in records}:
        rs = [r for r in records if r["kernel"] == kernel]
        meas = np.array([r["measured_s"] for r in rs])
        fit = sim_backends.fit_linear_cost(
            [r["flops"] for r in rs], [r["bytes"] for r in rs], meas)
        roof = roofline_pred(rs)
        table = sim_backends.table_from_samples(rs)
        t_err = max(abs(table._lookup(r["kind"], r["flops"])
                        - r["measured_s"]) / r["measured_s"] for r in rs)
        # a dropped term fits as an infinite rate — JSON-encode it as
        # null rather than the non-standard Infinity literal
        fin = lambda v: float(v) if math.isfinite(v) else None  # noqa: E731
        out[kernel] = {
            "n_samples": len(rs),
            "roofline_mape": sim_backends.mape(roof, meas),
            "fitted_mape": fit["mape"],
            "fitted": {"peak_flops_eff": fin(fit["peak_flops_eff"]),
                       "bw_eff": fin(fit["bw_eff"]),
                       "overhead_s": fin(fit["overhead_s"])},
            "table_max_rel_err": t_err,
        }
    return out


def table_backend(records: Sequence[Dict]) -> "sim_backends.TableBackend":
    """A measured-sample :class:`TableBackend` over every record — drop
    it into ``EngineConfig(cost_backend=...)`` to simulate with measured
    per-op times (the GUIDE's calibrate-then-simulate recipe)."""
    return sim_backends.table_from_samples(records)


def build_report(records: Sequence[Dict], meta: Dict,
                 fits: Optional[Dict[str, Dict]] = None) -> Dict:
    """The ``BENCH_calibration.json`` payload (sans recorded/budget)."""
    fits = calibrate(records) if fits is None else fits
    improved = sorted(k for k, f in fits.items()
                      if f["fitted_mape"] < f["roofline_mape"])
    return {
        "backend": meta["backend"], "interpret": meta["interpret"],
        "grid": meta["grid"], "repeat": meta["repeat"],
        "samples": list(records),
        "kernels": {k: fits[k] for k in sorted(fits)},
        "improved": improved,
        "n_improved": len(improved),
    }
