"""Readings that ``limits/<cell>.json`` is set from for a ``serve_ep``
cell, at the cell's own sizes:

    python3 chipbench/calibrate_ep.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out DIR] [--phase both|serve|reference] \
        [--stop-after SECONDS]

For each seed the program runs one batch of the cell's traffic through
``serve(..., mesh=...)`` and the sampled requests are compared with the
reference, as a run compares them (``mean_gap``).  For each control seed
three more readings are taken on the same served tokens, each the mean gap
of the tokens another computation would choose at every served position:
the reference in float8 (e4m3), the precision below the bf16 the model
serves in; the reference with one chip's share of the routed experts (the
last ``E / model`` of them) left out of every MoE layer, the fault; and
the reference with every product's operands rounded to bfloat16, a
witness of how far bf16 arithmetic alone moves the tokens.  One JSON line
per reading.

The reference runs on one device, so the work splits in two: ``--phase
serve`` runs the program on the cell's chips and writes each seed's
sampled prompts and served tokens to ``DIR/served_<seed>.npz``;
``--phase reference`` reads them back on a host with one chip and takes
the readings, writing beside them ``DIR/readings_<seed>.npz`` (the
reference's best two logits and each computation's chosen tokens with the
reference's logit of them), from which any other gap statistic can be
worked out afterwards.  ``--stop-after`` starts no new seed once that many
seconds have passed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench.bench import ROOT, load_cell, log  # noqa: E402


def served(spec: dict, tr: dict, seed: int):
    """(sampled prompts (n, P), their served tokens (n, N)) of one batch
    of the cell's traffic through the program on the cell's mesh."""
    import numpy as np
    from chipbench import traffic, weights_mla
    from chipbench.drivers.serve import _serve
    from chipbench.drivers.serve_ep import mesh_for, pick
    cfg = weights_mla.model_config(spec)
    mesh = mesh_for(spec)
    params = weights_mla.program_params(spec, cfg, seed, mesh)
    prompts = traffic.prompts(tr, spec["vocab_size"], seed, traffic.WINDOW, 1)
    r = _serve()(cfg, params, prompts, batch=tr["batch"],
                 max_new=tr["max_new"], emit=lambda _: None, mesh=mesh)
    chosen = pick(seed, tr, len(prompts))
    return np.stack(prompts)[chosen], np.asarray(r.tokens)[chosen]


def readings(spec: dict, tr: dict, seed: int, control: bool,
             prompts=None, tokens=None, keep: dict | None = None) -> list:
    """The program's reading and, with ``control``, the three others, each
    as ``reference.serve.gaps`` reads the served tokens.
    Serves the batch first unless its ``prompts`` and ``tokens`` are
    given; ``keep`` gathers the arrays that ``readings_<seed>.npz``
    holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench.reference.deepseek_v2 import served_logits
    if tokens is None:
        prompts, tokens = served(spec, tr, seed)
    t = time.perf_counter()
    ref = served_logits(spec, seed, prompts, tokens)
    top2 = np.asarray(jax.lax.top_k(ref, 2)[0])

    def at(chosen):
        return np.asarray(jnp.take_along_axis(
            ref, jnp.asarray(chosen)[..., None], -1)[..., 0])

    def row(who, chosen):
        g = top2[..., 0] - at(chosen)
        if keep is not None:
            keep[f"{who}.tokens"], keep[f"{who}.ref_logit"] = chosen, at(chosen)
        return {"who": who, "max_gap": float(g.max()),
                "mean_gap": float(g.mean()), "tokens": int(g.size),
                "differ": int((g > 0).sum())}
    if keep is not None:
        keep["ref.top2"] = top2
    out = [dict(row("program", tokens), reference_s=time.perf_counter() - t)]
    if control:
        E = spec["n_routed_experts"]
        share = E // spec["mesh"]["model"]
        for who, kw in (("control_fp8", {"low": "fp8"}),
                        ("fault_one_chip_experts",
                         {"drop_experts": range(E - share, E)}),
                        ("witness_bf16", {"low": "bf16"})):
            other = served_logits(spec, seed, prompts, tokens, **kw)
            out.append(row(who, np.asarray(jnp.argmax(other, -1))))
            del other
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "calibrate"))
    ap.add_argument("--phase", choices=("both", "serve", "reference"),
                    default="both")
    ap.add_argument("--stop-after", type=float, default=float("inf"))
    args = ap.parse_args()
    _, w, spec, tr = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np
    from chipbench import chip
    chip.use_compile_cache()
    if args.phase != "reference":
        chip.require_chips(w["chips"])
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    started = time.perf_counter()
    for seed in [int(s) for s in args.seeds.split(",")]:
        if time.perf_counter() - started > args.stop_after:
            log(f"[calibrate] stopped before seed {seed}: "
                f"{args.stop_after} s passed")
            break
        t = time.perf_counter()
        if args.phase == "reference":
            f = np.load(out / f"served_{seed}.npz")
            prompts, tokens = f["prompts"], f["tokens"]
        else:
            prompts, tokens = served(spec, tr, seed)
            np.savez(out / f"served_{seed}.npz", prompts=prompts,
                     tokens=tokens)
        log(f"[calibrate] seed {seed} served at "
            f"{time.perf_counter() - t:.1f} s")
        if args.phase == "serve":
            continue
        keep = {}
        for r in readings(spec, tr, seed, seed in controls, prompts, tokens,
                          keep):
            print(json.dumps(dict(workload=args.workload, seed=seed, **r)),
                  flush=True)
        np.savez(out / f"readings_{seed}.npz", **keep)
        log(f"[calibrate] seed {seed} took {time.perf_counter() - t:.1f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
