#!/usr/bin/env python
"""Calibrate the cost backends against the real Pallas kernels.

Times ``repro/kernels/`` (``nvdla_matmul``, ``flash_attention``,
``mamba_scan``) over a shape grid with best-of-k, fits per-kernel
(flops, bytes, overhead) cost parameters by least squares, and prints —
or writes — the calibration report.  The CI-gated artifact writer is
``benchmarks/bench_calibration.py``; this is the standalone harness for
poking at grids and repeats:

    PYTHONPATH=src python tools/calibrate.py --grid quick --interpret
    PYTHONPATH=src python tools/calibrate.py --repeat 5 --out cal.json

``--interpret`` runs the kernel bodies in Python, for a host with no TPU;
without it the kernels compile to Mosaic and need the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.kernels import calibrate  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--grid", choices=("full", "quick"), default="full")
    ap.add_argument("--repeat", type=int, default=3,
                    help="best-of-k repeats per shape (default 3)")
    ap.add_argument("--kernels", nargs="+", default=list(calibrate.KERNELS),
                    choices=list(calibrate.KERNELS),
                    help="subset of kernels to measure")
    ap.add_argument("--interpret", action="store_true",
                    help="interpret the kernels (a host with no TPU)")
    ap.add_argument("--out", type=pathlib.Path, default=None,
                    help="write the report JSON here instead of stdout")
    args = ap.parse_args()

    records, meta = calibrate.measure(grid=args.grid, repeat=args.repeat,
                                      kernels=args.kernels,
                                      interpret=args.interpret)
    report = calibrate.build_report(records, meta)
    text = json.dumps(report, indent=2, default=float) + "\n"
    if args.out:
        args.out.write_text(text)
        print(f"wrote {args.out}")
    else:
        sys.stdout.write(text)
    for name in sorted(report["kernels"]):
        f = report["kernels"][name]
        print(f"{name}: roofline_mape={f['roofline_mape']:.3g} -> "
              f"fitted_mape={f['fitted_mape']:.3g}", file=sys.stderr)


if __name__ == "__main__":
    main()
