"""End-to-end training driver: data pipeline -> sharded train step ->
async checkpointing -> restart/restore.  The same script scales from this
CPU container (--preset cpu-small: ~5M params, a few hundred steps) to the
full config (--preset full); repro.launch.train runs it over the local chips.

  PYTHONPATH=src python examples/train_lm.py --steps 60 --preset cpu-small
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import DataPipeline
from repro.train import TrainConfig, init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama_1_1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--preset", default="cpu-small",
                    choices=["cpu-small", "full"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = (get_smoke_config(args.arch) if args.preset == "cpu-small"
           else get_config(args.arch))
    # a ~5M-param config that actually trains in CPU minutes
    if args.preset == "cpu-small":
        cfg = dataclasses.replace(cfg, n_layers=4, d_model=256, n_heads=8,
                                  n_kv_heads=4, d_ff=704, vocab=2048)
    tc = TrainConfig(lr=1e-3, warmup=20, total_steps=args.steps)
    params, opt, axes, _ = init_train_state(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq}")

    step_fn = jax.jit(make_train_step(cfg, tc), donate_argnums=(0, 1))
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    start = 0
    if args.resume and mgr.latest_step() is not None:
        out = mgr.restore(template={"params": params, "opt": opt})
        params, opt = out["tree"]["params"], out["tree"]["opt"]
        start = out["step"] + 1
        print(f"resumed from step {out['step']}")

    pipe = DataPipeline(cfg, args.batch, args.seq, n_workers=2, prefetch=2)
    try:
        t0 = time.time()
        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            params, opt, metrics = step_fn(params, opt, batch,
                                           jnp.asarray(i, jnp.int32))
            if i % 10 == 0 or i == args.steps - 1:
                tok_s = (i - start + 1) * args.batch * args.seq \
                    / (time.time() - t0)
                print(f"step {i:4d} loss={float(metrics['loss']):.3f} "
                      f"gnorm={float(metrics['grad_norm']):.2f} "
                      f"tok/s={tok_s:.0f}", flush=True)
            if i and i % args.ckpt_every == 0:
                mgr.save_async(i, {"params": params, "opt": opt})
        mgr.save_async(args.steps - 1, {"params": params, "opt": opt})
        mgr.wait()
        print(f"done; checkpoints in {args.ckpt_dir}")
    finally:
        pipe.stop()


if __name__ == "__main__":
    main()
