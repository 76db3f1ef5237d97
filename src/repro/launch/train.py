"""Training launcher: builds the mesh, shards state per the rule engine,
and runs the train loop with fault-tolerant checkpointing.

  python -m repro.launch.train --arch tinyllama_1_1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.train --smoke

The mesh is data-parallel over the chips this process holds, and the full
config needs TPUs: without them the launcher exits instead of going on
on the CPU.  The default cell (tinyllama_1_1b, train_4k: 256 x 4096
tokens a step, about 13 GB of parameters, gradients and AdamW state
before any activation) needs more than one v5e chip's 16 GB.  ``--smoke`` runs the
reduced preset (batch 4, 64 tokens) on any device.  The 16x16 production mesh is lowered and compiled, not run,
by ``repro.launch.dryrun``.

Fault-tolerance posture (DESIGN.md §4): resume from the newest committed
checkpoint (``--resume``), async saves off the training thread, elastic
restore onto whatever mesh this launch built (checkpoints are mesh-
agnostic), preemption-safe atomic commits.

``--dry-run`` skips the JAX path entirely and prices the SAME
(arch x shape x microbatches) cell through the training simulator
(``repro.sim.training``): predicted step time, tokens/s, per-stage
utilization and pipeline bubble under GPipe and 1F1B at ``--stages``
pipeline stages — the pre-launch sanity check for a schedule choice.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core.config import SHAPE_BY_NAME
from repro.data import DataPipeline
from repro.dist import context as dist_ctx
from repro.dist.sharding import rules_for, set_active_rules
from repro.launch.chip import require_tpu, use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.train import TrainConfig, make_train_step


def dry_run(arch: str, shape_name: str, *, n_stages: int = 1,
            n_microbatches: int = 1, schedule: str = "both",
            smoke: bool = False, emit=print):
    """Price the (arch x shape x microbatches) training cell through the
    simulator instead of launching it; returns the ``TrainingResult``
    list (one per schedule)."""
    from repro.sim.training import SCHEDULES, simulate_training
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    batch, seq = (4, 64) if smoke else (shape.global_batch, shape.seq_len)
    schedules = SCHEDULES if schedule == "both" else (schedule,)
    out = []
    for sched in schedules:
        r = simulate_training(cfg, n_stages=n_stages,
                              n_microbatches=n_microbatches,
                              schedule=sched, seq_len=seq,
                              global_batch=batch)
        out.append(r)
        utils = " ".join(f"{k}={v:.2f}"
                         for k, v in r.per_stage_utilization.items())
        emit(f"[dry-run] {arch}/{shape_name} {sched} p={n_stages} "
             f"m={n_microbatches}: step={r.step_time_s*1e3:.3f}ms "
             f"({r.tokens_per_s:.0f} tok/s) "
             f"bubble={r.bubble_fraction:.3f} "
             f"(bound {r.bubble_bound:.3f}) {utils}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--dry-run", action="store_true",
                    help="simulate the step instead of launching it")
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stages for --dry-run")
    ap.add_argument("--schedule", default="both",
                    choices=("gpipe", "1f1b", "both"),
                    help="pipeline schedule(s) for --dry-run")
    args = ap.parse_args()

    if args.dry_run:
        dry_run(args.arch, args.shape, n_stages=args.stages,
                n_microbatches=args.microbatches, schedule=args.schedule,
                smoke=args.smoke)
        return

    shape = SHAPE_BY_NAME[args.shape]
    if args.smoke:
        cfg = get_smoke_config(args.arch)
        batch, seq = 4, 64
    else:
        require_tpu()
        cfg = get_config(args.arch)
        batch, seq = shape.global_batch, shape.seq_len
    use_compile_cache()
    mesh = make_host_mesh(len(jax.devices()), 1)

    rules = rules_for(cfg, shape, mesh)
    set_active_rules(rules)
    dist_ctx.set_mesh(mesh)

    params, axes = T.init_params(cfg, jax.random.PRNGKey(0))
    param_sh = rules.tree_shardings(
        axes, jax.tree_util.tree_map(lambda x: x, params))
    params = jax.device_put(params, param_sh)
    opt = jax.device_put(adamw_init(params), {
        "m": param_sh, "v": param_sh,
        "count": jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())})

    tc = TrainConfig(total_steps=args.steps,
                     n_microbatches=args.microbatches)
    step_fn = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start = 0
    if args.resume and mgr.latest_step() is not None:
        out = mgr.restore(template={"params": params, "opt": opt},
                          shardings={"params": param_sh,
                                     "opt": {"m": param_sh, "v": param_sh,
                                             "count": None}})
        params, opt = out["tree"]["params"], out["tree"]["opt"]
        start = out["step"] + 1
        print(f"[restore] resumed at step {start}")

    pipe = DataPipeline(cfg, batch, seq, n_workers=2, prefetch=2)
    try:
        t0 = time.time()
        for i in range(start, args.steps):
            b = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            params, opt, metrics = step_fn(params, opt, b,
                                           jnp.asarray(i, jnp.int32))
            if i % 10 == 0:
                print(f"step {i} loss={float(metrics['loss']):.3f} "
                      f"({(i-start+1)*batch*seq/(time.time()-t0):.0f} tok/s)",
                      flush=True)
            if i and i % args.ckpt_every == 0:
                mgr.save_async(i, {"params": params, "opt": opt})
        mgr.save_async(args.steps - 1, {"params": params, "opt": opt})
        mgr.wait()
    finally:
        pipe.stop()
        set_active_rules(None)
        dist_ctx.set_mesh(None)


if __name__ == "__main__":
    main()
