"""Readings that the limits in ``limits/<cell>.json`` are set from, on the
chip at the cell's own sizes, all in one process:

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3]

For each seed the program runs the cell's timed path once (one batch of
the cell's traffic through ``serve()``, or the training job's first steps
through the compiled step) and is compared with the reference, as a run
compares it.  For each control seed the control is read too: the
reference computed in float8 put in the program's place, and for training
also the fault of half of each batch left out.  One JSON line per reading.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.bench import ROOT, Cell, load_cell, log  # noqa: E402


def serve_readings(cell: Cell, control: bool) -> list:
    import jax
    import numpy as np
    from chipbench import traffic, weights
    from chipbench.drivers.serve import _serve
    from chipbench.reference.serve import gaps, served_logits
    from chipbench.spec import model_config
    tr = cell.traffic
    cfg = model_config(cell.spec)
    params = weights.program_params(cell.spec, cfg, cell.seed)
    prompts = traffic.prompts(tr, cell.spec["vocab_size"], cell.seed,
                              traffic.WINDOW, 1)
    r = _serve()(cfg, params, prompts, batch=tr["batch"],
                 max_new=tr["max_new"], emit=lambda _: None)
    tokens = r.tokens
    del r, params
    pick = np.sort(np.random.default_rng([cell.seed, 4]).choice(
        len(prompts), tr["check_requests"], replace=False))
    served, prompts = tokens[pick], np.stack(prompts)[pick]
    t = time.perf_counter()
    ref = served_logits(cell.spec, cell.seed, prompts, served)
    g = gaps(ref, served)
    out = [{"who": "program", "max_gap": float(g.max()),
            "mean_gap": float(g.mean()), "tokens": int(g.size),
            "differ": int((g > 0).sum()),
            "reference_s": time.perf_counter() - t}]
    if control:
        ctl = served_logits(cell.spec, cell.seed, prompts, served, fp8=True)
        cg = gaps(ref, np.asarray(jax.numpy.argmax(ctl, -1)))
        out.append({"who": "control_fp8", "max_gap": float(cg.max()),
                    "mean_gap": float(cg.mean()),
                    "differ": int((cg > 0).sum())})
    return out


def train_readings(cell: Cell, control: bool) -> list:
    from chipbench.drivers.train import CHECKED_STEPS, compare, first_steps
    from chipbench.reference import train as ref_train
    step, params, opt, prog, _, _ = first_steps(cell)
    del step, params, opt
    t = time.perf_counter()
    ref = ref_train.first_steps(cell.spec, cell.seed, cell.traffic,
                                CHECKED_STEPS)
    out = [dict(who="program", reference_s=time.perf_counter() - t,
                **compare(prog, ref))]
    if control:
        ctl = ref_train.first_steps(cell.spec, cell.seed, cell.traffic,
                                    CHECKED_STEPS, fp8=True)
        out.append(dict(who="control_fp8", **compare(ctl, ref)))
        half = ref_train.first_steps(cell.spec, cell.seed, cell.traffic,
                                     CHECKED_STEPS,
                                     rows=cell.traffic["batch"] // 2)
        out.append(dict(who="fault_half_batch", **compare(half, ref)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    _, w, spec, tr = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import chip
    chip.use_compile_cache()
    dev = chip.require_chips(w["chips"])[0]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    read = {"serve": serve_readings, "train": train_readings}[tr["kind"]]
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        cell = Cell(args.workload, spec, tr, seed, 0.0, False, {},
                    chip.peaks(dev.device_kind), t)
        for r in read(cell, seed in controls):
            print(json.dumps(dict(workload=args.workload, seed=seed, **r)),
                  flush=True)
        log(f"[calibrate] seed {seed} took {time.perf_counter() - t:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
