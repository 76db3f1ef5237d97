"""Hashes of the optimized TPU v5e HLO of the benchmark cells' steps,
compiled for a described v5e with no chip attached, so that two checkouts
can be compared program by program:

    JAX_PLATFORMS=cpu python tools/hlo_hash.py <checkout> <out dir>

For each cell of ``<checkout>/BENCHMARK.json`` it compiles, at the cell's
own shapes, what the cell runs: a one-chip serving cell's prefill step and
its donated decode step as ``repro.launch.serve.serve()`` jits them without
a mesh, a training cell's donated train step, and a four-chip serving
cell's prefill and donated decode as ``serve(..., mesh=...)`` jits them on
the configuration's ``data`` x ``model`` mesh over a described
``v5e:2x2``, with ``serve``'s shardings and rules installed.  Each program's text is written
to ``<out dir>/<cell>.<step>.hlo`` with its metadata left out: the source
locations (``metadata={...}`` and the file, function and stack-frame tables
ahead of the first computation) and the module's name.  One line per program
(name, the first 16 hex digits of its SHA-256, lines) goes to stdout and all
of them to ``<out dir>/hashes.json``.  Load the TPU compiler in one process
at a time.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import sys


def strip(text: str) -> str:
    """The program without its source locations and module name."""
    lines = text.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    kept = [re.sub(r"^HloModule \S+", "HloModule", lines[0])] + lines[first:]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(kept))


def main(root: str, out: str) -> int:
    sys.path[:0] = [root, os.path.join(root, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench.spec import model_config
    from repro.models import transformer as T
    from repro.serve import make_decode_step, make_prefill_step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    def write(cell, progs):
        for name, compiled in progs.items():
            text = strip(compiled.as_text())
            key = f"{cell}.{name}"
            with open(os.path.join(out, key + ".hlo"), "w") as f:
                f.write(text)
            hashes[key] = hashlib.sha256(text.encode()).hexdigest()[:16]
            print(key, hashes[key], len(text.splitlines()), flush=True)

    def on_mesh(spec, cfg, tr):
        """The prefill and donated decode of ``serve(..., mesh=...)``."""
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from repro.launch import serve as SV

        def placed(tree, shardings):
            return jax.tree_util.tree_map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                tree, shardings)
        shape = (spec["mesh"]["data"], spec["mesh"]["model"])
        mesh = Mesh(np.asarray(topo.devices[:shape[0] * shape[1]])
                    .reshape(shape), ("data", "model"))
        B, P, N = tr["batch"], tr["prompt_len"], tr["max_new"]
        rules, params_sh, cache_sh, rows = SV._on_mesh(cfg, mesh, B, P + N)
        rep = NamedSharding(mesh, PartitionSpec())
        params = placed(jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.key(0))[0]), params_sh)
        cache = placed(jax.eval_shape(
            lambda: T.init_cache(cfg, B, P + N)[0]), cache_sh)
        with SV._installed(mesh, rules):
            prefill = jax.jit(make_prefill_step(cfg, max_seq=P + N),
                              in_shardings=(params_sh, rows),
                              out_shardings=(rep, cache_sh))
            decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,),
                             in_shardings=(params_sh, cache_sh, rows, rep),
                             out_shardings=(rows, cache_sh))
            return {
                "prefill": prefill.lower(params, {
                    "tokens": jax.ShapeDtypeStruct((B, P), jnp.int32,
                                                   sharding=rows)}).compile(),
                "decode": decode.lower(
                    params, cache,
                    jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=rows),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
                ).compile()}

    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    os.makedirs(out, exist_ok=True)
    hashes = {}
    for w in bench["workloads"]:
        spec = json.load(open(os.path.join(root, files[w["config"]])))
        tr = json.load(open(os.path.join(root, "chipbench", "traffic",
                                         w["traffic"] + ".json")))
        if w["chips"] != 1:
            from chipbench import weights_mla
            cfg = weights_mla.model_config(spec)
            write(w["name"], on_mesh(spec, cfg, tr))
            continue
        cfg = model_config(spec)
        params = on(jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.key(0))[0]))
        progs = {}
        if tr["kind"] == "serve":
            B, P, N = tr["batch"], tr["prompt_len"], tr["max_new"]
            prefill = jax.jit(make_prefill_step(cfg, max_seq=P + N))
            inputs = {"tokens": arg((B, P))}
            progs["prefill"] = prefill.lower(params, inputs).compile()
            cache = on(jax.eval_shape(prefill, params, inputs)[1])
            decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
            progs["decode"] = decode.lower(params, cache, arg((B, 1)),
                                           arg(())).compile()
        else:
            import repro.train as RT
            from repro.optim import adamw_init
            hp = tr["optimizer"]
            tc = RT.TrainConfig(lr=hp["lr"], warmup=hp["warmup"],
                                total_steps=hp["total_steps"],
                                grad_clip=hp["grad_clip"],
                                weight_decay=hp["weight_decay"])
            opt = on(jax.eval_shape(adamw_init, params))
            batch = {k: arg((tr["batch"], tr["seq_len"]))
                     for k in ("tokens", "labels")}
            step = jax.jit(RT.make_train_step(cfg, tc), donate_argnums=(0, 1))
            progs["train"] = step.lower(params, opt, batch,
                                        arg(())).compile()
        write(w["name"], progs)
    with open(os.path.join(out, "hashes.json"), "w") as f:
        json.dump(hashes, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
