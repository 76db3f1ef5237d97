"""Calibration benchmark: measured Pallas kernels vs the cost backends.

Times the real ``repro/kernels/`` Pallas kernels (``nvdla_matmul``,
``flash_attention``, ``mamba_scan``) over a shape grid
(``repro.kernels.calibrate``), fits per-kernel cost parameters by least
squares, and writes ``BENCH_calibration.json`` at the repo root.

Gates (both modes):

* **n_improved >= 2** — the fitted model's MAPE must beat the
  uncalibrated roofline (at the canonical TPU constants) on at least 2
  of the 3 kernels.
* **matmul MAPE floor** — the fitted matmul error must stay under
  ``MATMUL_MAPE_CEIL``; a linear (flops, bytes, overhead) model that
  cannot track its own measured matmul grid means the accounting broke.
* **table round-trip** — the measured ``TableBackend`` must reproduce
  every sample it was built from bit-exactly.

``--quick`` (the ``tools/ci.sh`` smoke) re-measures the 2-shape quick
grid, re-runs the gates, and checks the measurement wall against the
recorded quick budget (2x gate).  Full mode runs the full grid and
records the artifact.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax

from repro.kernels import calibrate
from repro.sim.report import row

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_JSON = ROOT / "BENCH_calibration.json"

N_IMPROVED_FLOOR = 2          # fitted beats roofline on >= 2 of 3 kernels
MATMUL_MAPE_CEIL = 0.35       # fitted matmul MAPE must stay under this
TABLE_RT_TOL = 1e-12          # measured table reproduces its own samples


def measure(full: bool):
    grid = "full" if full else "quick"
    t0 = time.perf_counter()
    records, meta = calibrate.measure(grid=grid, repeat=3 if full else 2,
                                      interpret=True)
    t_measure = time.perf_counter() - t0
    out = calibrate.build_report(records, meta)
    out["budget_s"] = {f"measure_{grid}_grid": round(t_measure, 6)}

    rows = []
    for name in sorted(out["kernels"]):
        f = out["kernels"][name]
        rows.append(row(
            f"calibration/{name}", f["fitted"]["overhead_s"] or 0.0,
            f"n={f['n_samples']} roofline_mape={f['roofline_mape']:.3g} "
            f"fitted_mape={f['fitted_mape']:.3g} "
            f"backend={out['backend']}"))
    rows.append(row(
        f"calibration/measure_{grid}_grid", t_measure,
        f"n_samples={len(records)} n_improved={out['n_improved']} "
        f"interpret={out['interpret']}"))
    return out, rows


def _check(out):
    """The modeled-vs-measured gates (same in quick and full mode)."""
    failed = False
    if out["n_improved"] < N_IMPROVED_FLOOR:
        print(f"calibration smoke: fitted model beat the roofline on only "
              f"{out['n_improved']} kernels (floor {N_IMPROVED_FLOOR}); "
              f"improved={out['improved']}", file=sys.stderr)
        failed = True
    mm = out["kernels"].get("matmul")
    if mm is None or mm["fitted_mape"] > MATMUL_MAPE_CEIL:
        got = None if mm is None else round(mm["fitted_mape"], 4)
        print(f"calibration smoke: matmul fitted MAPE {got} over the "
              f"{MATMUL_MAPE_CEIL} ceiling", file=sys.stderr)
        failed = True
    worst_rt = max(f["table_max_rel_err"] for f in out["kernels"].values())
    if worst_rt > TABLE_RT_TOL:
        print(f"calibration smoke: TableBackend round-trip error "
              f"{worst_rt} > {TABLE_RT_TOL}", file=sys.stderr)
        failed = True
    return failed


def run(emit=print):
    """benchmarks.run driver entry: quick-grid rows only (no file writes)."""
    _, rows = measure(full=False)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="quick-grid re-measure + the n_improved / matmul "
                         "MAPE / table round-trip gates + the 2x budget "
                         "gate vs BENCH_calibration.json (CI smoke)")
    args = ap.parse_args()
    out, rows = measure(full=not args.quick)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},\"{r['derived']}\"")
    failed = _check(out)
    if args.quick:
        if not BENCH_JSON.exists():
            print(f"no {BENCH_JSON.name}; run without --quick to record "
                  "budgets", file=sys.stderr)
            sys.exit(1)
        recorded = json.loads(BENCH_JSON.read_text())
        for name, measured in out["budget_s"].items():
            budget = recorded.get("budget_s", {}).get(name)
            if budget is None:
                continue
            verdict = "OK" if measured <= 2.0 * budget else "REGRESSION"
            print(f"perf-smoke {name}: {measured*1e3:.1f}ms vs budget "
                  f"{budget*1e3:.1f}ms (2x gate) {verdict}")
            failed |= verdict != "OK"
        if recorded.get("n_improved", 0) < N_IMPROVED_FLOOR:
            print(f"calibration smoke: recorded artifact has n_improved="
                  f"{recorded.get('n_improved')}", file=sys.stderr)
            failed = True
        if failed:
            print("bench_calibration smoke failed (a calibration gate "
                  "broke or measurement went >2x budget)", file=sys.stderr)
            sys.exit(1)
        return
    if failed:
        sys.exit(1)
    # record the quick-grid budget too, so --quick has one to gate on.
    # --quick pays kernel tracing inside its measured wall, so this
    # process drops its compiled kernels first: a warm-cache budget would
    # gate every cold CI run as a false regression.  It stays in this
    # process, since a child could not reach a chip this one holds.
    jax.clear_caches()
    t0 = time.perf_counter()
    calibrate.measure(grid="quick", repeat=2, interpret=True)
    out["budget_s"]["measure_quick_grid"] = round(
        time.perf_counter() - t0, 6)
    out["recorded"] = time.strftime("%Y-%m-%d")
    out["note"] = ("best-of-k wall times of the interpret-mode Pallas "
                   "kernels over the full shape grid; per-kernel "
                   "least-squares (flops, bytes, overhead) fits vs the "
                   "uncalibrated TPU-constant roofline; budget_s feeds "
                   "the tools/ci.sh --quick 2x gate")
    BENCH_JSON.write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {BENCH_JSON}")


if __name__ == "__main__":
    main()
