import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any jax import: jax locks the device
#   count on first init.  Do not set this anywhere global (tests/benches see
#   one device).

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell, print memory/cost analysis, and record roofline inputs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch ID] [--shape NAME]
      [--mesh single|multi|both] [--out experiments/dryrun]

Results (memory analysis, cost analysis, parsed collective bytes, HLO loop
tree) are appended incrementally to <out>/results.json so the sweep is
resumable; cells already present are skipped unless --force.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core.config import SHAPES, ModelConfig, ShapeConfig, cell_is_runnable
from repro.dist import context as dist_ctx
from repro.dist.sharding import Rules, rules_for, set_active_rules
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.train import TrainConfig, make_train_step


# ---------------------------------------------------------------------------
# abstract inputs


def abstract_params(cfg: ModelConfig):
    """(params as ShapeDtypeStructs, logical-axes pytree) — no allocation.
    The axes tree is static python data, captured via a side cell while
    eval_shape traces the array part."""
    holder = {}

    def f(k):
        params, axes = T.init_params(cfg, k)
        holder["axes"] = axes
        return params

    params = jax.eval_shape(f, jax.random.PRNGKey(0))
    return params, holder["axes"]


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int):
    holder = {}

    def f():
        cache, axes = T.init_cache(cfg, batch, max_seq)
        holder["axes"] = axes
        return cache

    cache = jax.eval_shape(f)
    return cache, holder["axes"]


def input_specs(cfg: ModelConfig, shape: ShapeConfig, rules: Rules):
    """ShapeDtypeStruct stand-ins for every model input of this cell, plus
    their NamedShardings.  No device allocation happens here."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    batch_spec = rules.spec_for(("batch", None), (B, S))

    def sharded(spec_axes, struct):
        return NamedSharding(rules.mesh,
                             rules.spec_for(spec_axes, struct.shape)), struct

    if shape.kind == "train":
        batch = {"tokens": sds((B, S), jnp.int32),
                 "labels": sds((B, S), jnp.int32)}
        bshard = {"tokens": NamedSharding(rules.mesh, batch_spec),
                  "labels": NamedSharding(rules.mesh, batch_spec)}
        if cfg.family == "encdec":
            batch["frames"] = sds((B, cfg.encoder.n_ctx, cfg.d_model),
                                  jnp.float32)
            bshard["frames"] = NamedSharding(
                rules.mesh, rules.spec_for(("batch", None, None),
                                           batch["frames"].shape))
        if cfg.family == "vlm":
            batch["patches"] = sds((B, cfg.n_patches, cfg.d_model),
                                   jnp.float32)
            bshard["patches"] = NamedSharding(
                rules.mesh, rules.spec_for(("batch", None, None),
                                           batch["patches"].shape))
        return batch, bshard

    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32)}
        bshard = {"tokens": NamedSharding(rules.mesh, batch_spec)}
        if cfg.family == "encdec":
            batch["frames"] = sds((B, cfg.encoder.n_ctx, cfg.d_model),
                                  jnp.float32)
            bshard["frames"] = NamedSharding(
                rules.mesh, rules.spec_for(("batch", None, None),
                                           batch["frames"].shape))
        if cfg.family == "vlm":
            batch["patches"] = sds((B, cfg.n_patches, cfg.d_model),
                                   jnp.float32)
            bshard["patches"] = NamedSharding(
                rules.mesh, rules.spec_for(("batch", None, None),
                                           batch["patches"].shape))
        return batch, bshard

    # decode: cache + one token
    cache, cache_axes = abstract_cache(cfg, B, S)
    cache_sh = rules.tree_shardings(cache_axes, cache)
    tokens = sds((B, 1), jnp.int32)
    tok_sh = NamedSharding(rules.mesh, rules.spec_for(("batch", None),
                                                      (B, 1)))
    return (cache, tokens), (cache_sh, tok_sh)


# ---------------------------------------------------------------------------
# lowering per cell


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
               n_microbatches: int = 1, donate: bool = True):
    """Returns (lowered, rules).  Raises on sharding/lowering failure."""
    rules = rules_for(cfg, shape, mesh)
    set_active_rules(rules)
    dist_ctx.set_mesh(mesh)
    params, axes = abstract_params(cfg)
    param_sh = rules.tree_shardings(axes, params)

    if shape.kind == "train":
        from repro.optim import adamw_init
        tc = TrainConfig(n_microbatches=n_microbatches)
        step_fn = make_train_step(cfg, tc)
        opt = jax.eval_shape(adamw_init, params)
        opt_sh = {"m": param_sh, "v": param_sh,
                  "count": NamedSharding(mesh, P())}
        batch, batch_sh = input_specs(cfg, shape, rules)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32)
        f = jax.jit(step_fn,
                    in_shardings=(param_sh, opt_sh, batch_sh,
                                  NamedSharding(mesh, P())),
                    out_shardings=(param_sh, opt_sh, None),
                    donate_argnums=(0, 1) if donate else ())
        return f.lower(params, opt, batch, step_sds), rules

    if shape.kind == "prefill":
        from repro.serve import make_prefill_step
        step_fn = make_prefill_step(cfg, max_seq=shape.seq_len)
        batch, batch_sh = input_specs(cfg, shape, rules)
        f = jax.jit(step_fn, in_shardings=(param_sh, batch_sh))
        return f.lower(params, batch), rules

    # decode
    from repro.serve import make_decode_step
    step_fn = make_decode_step(cfg)
    (cache, tokens), (cache_sh, tok_sh) = input_specs(cfg, shape, rules)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    f = jax.jit(step_fn,
                in_shardings=(param_sh, cache_sh, tok_sh,
                              NamedSharding(mesh, P())),
                out_shardings=(tok_sh, cache_sh),
                donate_argnums=(1,) if donate else ())
    return f.lower(params, cache, tokens, pos_sds), rules


def run_cell(arch: str, shape: ShapeConfig, mesh, mesh_name: str,
             out_dir: Path, *, save_hlo: bool = False,
             n_microbatches: int = 1):
    """Lower + compile one cell; return the result record."""
    cfg = get_config(arch)
    runnable, why = cell_is_runnable(cfg, shape)
    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
           "kind": shape.kind, "timestamp": time.time()}
    if not runnable:
        rec.update(status="skip", reason=why)
        return rec
    t0 = time.time()
    try:
        lowered, rules = lower_cell(cfg, shape, mesh,
                                    n_microbatches=n_microbatches)
        t_lower = time.time() - t0
        print(f"  lowered in {t_lower:.1f}s", flush=True)
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        print(f"  compiled in {t_compile:.1f}s", flush=True)
        mem = compiled.memory_analysis()
        print("  memory_analysis done", flush=True)
        cost = compiled.cost_analysis()
        print("  cost_analysis done", flush=True)
        hlo_text = compiled.as_text()
        print(f"  as_text done ({len(hlo_text)/1e6:.1f} MB)", flush=True)
        from repro.core.hlo import analyze_hlo
        hlo = analyze_hlo(hlo_text)
        print("  hlo analyzed", flush=True)
        if save_hlo:
            (out_dir / f"{arch}.{shape.name}.{mesh_name}.hlo.txt").write_text(
                hlo_text)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            cost={k: v for k, v in cost.items()
                  if not k.startswith("utilization")},
            hlo=hlo,
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    finally:
        set_active_rules(None)
        dist_ctx.set_mesh(None)
    return rec


# ---------------------------------------------------------------------------
# sweep driver (resumable)


def load_results(path: Path):
    if path.exists():
        return json.loads(path.read_text())
    return {}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    res_path = out_dir / "results.json"
    results = load_results(res_path)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = SHAPES if args.shape == "all" else [
        s for s in SHAPES if s.name == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    for multi in meshes:
        mesh_name = "pod2x16x16" if multi else "pod16x16"
        mesh = make_production_mesh(multi_pod=multi)
        for arch in archs:
            for shape in shapes:
                key = f"{arch}|{shape.name}|{mesh_name}"
                if args.microbatches > 1:
                    key += f"|mb{args.microbatches}"
                if key in results and not args.force \
                        and results[key]["status"] in ("ok", "skip"):
                    print(f"[cached] {key}: {results[key]['status']}")
                    continue
                print(f"[run] {key} ...", flush=True)
                rec = run_cell(arch, shape, mesh, mesh_name, out_dir,
                               save_hlo=args.save_hlo,
                               n_microbatches=args.microbatches)
                results[key] = rec
                res_path.write_text(json.dumps(results, indent=1))
                status = rec["status"]
                extra = (f" compile={rec.get('compile_s')}s"
                         if status == "ok" else
                         f" {rec.get('reason') or rec.get('error')}")
                print(f"[done] {key}: {status}{extra}", flush=True)

    ok = sum(1 for r in results.values() if r["status"] == "ok")
    skip = sum(1 for r in results.values() if r["status"] == "skip")
    err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\nTOTAL ok={ok} skip={skip} error={err}")
    return 0 if err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
