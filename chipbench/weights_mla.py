"""DeepSeek-V2's configuration as run, and its random weights from the seed,
made by the benchmark for the program and for the reference alike.

``dims`` reads the configuration file's published keys; ``model_config``
builds the program's ``ModelConfig`` from them.  The table of leaves is
worked out from the file, not from the program: each leaf has a name
(``attn.q``, ``moe.gate``, ``mlp.up`` ...), a shape, the dtype it is served
in, a scale and the mesh axes it is split over in the deployment.  Every
value is ``chipbench.weights._leaf`` of the seed, the leaf's name and its
layer (0 for the dense layer, 1 .. L-1 for the MoE layers), so the program
gets each leaf made directly into its sharding, one leaf a jitted call, and
no chip ever holds the whole model; the reference regenerates any one
layer bit for bit on one device.  ``program_layout`` checks that the
program's parameter tree has exactly these leaves, shapes, dtypes and
shardings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.weights import base_key, _leaf

BF16, F32 = jnp.bfloat16, jnp.float32
MODEL = "model"


def dims(spec: dict) -> dict:
    """The sizes the reference and the counts read, by short name."""
    y = spec["rope_scaling"]
    return {
        "L": spec["num_hidden_layers"], "nd": spec["first_k_dense_replace"],
        "d": spec["hidden_size"], "H": spec["num_attention_heads"],
        "R": spec["kv_lora_rank"], "dn": spec["qk_nope_head_dim"],
        "dr": spec["qk_rope_head_dim"], "dv": spec["v_head_dim"],
        "ff_dense": spec["intermediate_size"],
        "ff": spec["moe_intermediate_size"],
        "E": spec["n_routed_experts"], "k": spec["num_experts_per_tok"],
        "shared": spec["n_shared_experts"],
        "norm_topk": bool(spec["norm_topk_prob"]),
        "route_scale": float(spec["routed_scaling_factor"]),
        "V": spec["vocab_size"], "theta": float(spec["rope_theta"]),
        "eps": float(spec["rms_norm_eps"]),
        "yarn_factor": float(y["factor"]),
        "yarn_original": int(y["original_max_position_embeddings"]),
        "yarn_beta_fast": float(y["beta_fast"]),
        "yarn_beta_slow": float(y["beta_slow"]),
        "yarn_mscale": float(y["mscale"]),
        "yarn_mscale_all_dim": float(y["mscale_all_dim"]),
    }


def model_config(spec: dict):
    """The program's ``ModelConfig`` at the file's sizes, with dropless
    routing (capacity factor E / k)."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.config import MLAConfig, YaRNConfig
    base = get_config(spec["registry"])
    m = dims(spec)
    if spec["q_lora_rank"] or spec["rope_scaling"]["type"] != "yarn":
        raise ValueError("the program runs MLA without q compression and "
                         "with YaRN rope only")
    moe = dataclasses.replace(
        base.moe, n_experts=m["E"], top_k=m["k"], n_shared=m["shared"],
        d_ff_expert=m["ff"], capacity_factor=m["E"] / m["k"],
        norm_topk_prob=m["norm_topk"], routed_scaling_factor=m["route_scale"])
    return dataclasses.replace(
        base, n_layers=m["L"], n_dense_layers=m["nd"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["H"], d_ff=m["ff_dense"], vocab=m["V"],
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        rope_theta=m["theta"], moe=moe,
        mla=MLAConfig(kv_lora_rank=m["R"], q_lora_rank=0,
                      qk_nope_dim=m["dn"], qk_rope_dim=m["dr"],
                      v_head_dim=m["dv"]),
        yarn=YaRNConfig(factor=m["yarn_factor"],
                        original_max_position=m["yarn_original"],
                        beta_fast=m["yarn_beta_fast"],
                        beta_slow=m["yarn_beta_slow"],
                        mscale=m["yarn_mscale"],
                        mscale_all_dim=m["yarn_mscale_all_dim"]))


def table(spec: dict) -> tuple[dict, dict, dict]:
    """(global leaves, dense-layer leaves, MoE-layer leaves): name ->
    (shape, dtype, kind, scale, mesh axes of each dim).  ``kind`` is
    "normal" or "norm", as in ``chipbench.weights.table``."""
    m = dims(spec)
    d, H, R, V = m["d"], m["H"], m["R"], m["V"]
    dn, dr, dv = m["dn"], m["dr"], m["dv"]

    def w(shape, split):
        return shape, BF16, "normal", 1.0 / math.sqrt(shape[-2]), split

    def norm(n):
        return (n,), F32, "norm", 0.1, (None,)

    cols, rows = (None, MODEL), (MODEL, None)
    glob = {"embed": ((V, d), BF16, "normal", 0.02, rows),
            "final_norm": norm(d),
            "lm_head": w((d, V), cols)}
    common = {"norm1": norm(d), "norm2": norm(d),
              "attn.q": w((d, H * (dn + dr)), cols),
              "attn.kv_a": w((d, R + dr), (None, None)),
              "attn.kv_norm": norm(R),
              "attn.kv_b": w((R, H * (dn + dv)), cols),
              "attn.o": w((H * dv, d), rows)}
    ff, fs, E = m["ff_dense"], m["shared"] * m["ff"], m["E"]
    dense = dict(common, **{"mlp.gate": w((d, ff), cols),
                            "mlp.up": w((d, ff), cols),
                            "mlp.down": w((ff, d), rows)})
    experts = (MODEL, None, None)
    moe = dict(common, **{
        "moe.router": ((d, E), F32, "normal", 1.0 / math.sqrt(d),
                       (None, None)),
        "moe.gate": w((E, d, m["ff"]), experts),
        "moe.up": w((E, d, m["ff"]), experts),
        "moe.down": w((E, m["ff"], d), experts),
        "moe.shared.gate": w((d, fs), cols),
        "moe.shared.up": w((d, fs), cols),
        "moe.shared.down": w((fs, d), rows)})
    return glob, dense, moe


# the program's stacks: tree key -> (table index, first layer)
STACKS = {"dense_layers": 1, "layers": 2}


def _stack_layers(spec: dict, key: str) -> tuple[int, int]:
    """(first layer, layers) of the program's stack ``key``."""
    m = dims(spec)
    return (0, m["nd"]) if key == "dense_layers" else (m["nd"],
                                                       m["L"] - m["nd"])


def program_layout(spec: dict, cfg, mesh):
    """The program's parameter tree and its shardings on ``mesh``, checked
    leaf by leaf against the table.  Returns (treedef, [(name, stack key
    or None, sharding)])."""
    from jax.sharding import PartitionSpec
    from repro.core.config import ShapeConfig
    from repro.dist.sharding import rules_for
    from repro.models import transformer as T
    tables = table(spec)
    axes = {}

    def shapes():
        params, axes["axes"] = T.init_params(cfg, jax.random.key(0))
        return params
    abstract = jax.eval_shape(shapes)
    rules = rules_for(cfg, ShapeConfig("serve", 1, 1, "decode"), mesh)
    shardings = rules.tree_shardings(axes["axes"], abstract)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    out, seen = [], set()
    for (path, leaf), sh in zip(flat, treedef.flatten_up_to(shardings)):
        keys = [p.key for p in path]
        stack = keys[0] if keys[0] in STACKS else None
        name = ".".join(keys[1:] if stack else keys)
        entry = tables[STACKS[stack] if stack else 0].get(name)
        want = None
        if entry is not None:
            n = _stack_layers(spec, stack)[1] if stack else None
            want = ((n,) + entry[0] if stack else entry[0], entry[1],
                    PartitionSpec(*(((None,) if stack else ()) + entry[4])))
        got = (tuple(leaf.shape), leaf.dtype, _canonical(sh.spec, leaf.ndim))
        if want is None or got != (want[0], want[1],
                                   _canonical(want[2], leaf.ndim)):
            raise ValueError(f"the program's leaf {stack or ''}/{name} "
                             f"{got} is not in the benchmark's table as "
                             f"{want}")
        out.append((name, stack, sh))
        seen.add((stack, name))
    missing = {(s, n) for i, s in ((0, None), (1, "dense_layers"),
                                   (2, "layers"))
               for n in tables[i]} - seen
    if missing:
        raise ValueError(f"the program has no leaves {sorted(missing)}")
    return treedef, out


def _canonical(spec, ndim: int) -> tuple:
    """A PartitionSpec as a tuple of one entry a dimension."""
    t = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    return tuple(None if e in (None, ()) else e for e in t)


def program_params(spec: dict, cfg, seed: int, mesh):
    """The program's parameter tree filled with the benchmark's weights,
    each leaf made on the devices of ``mesh`` directly in the sharding
    the program runs it in."""
    treedef, names = program_layout(spec, cfg, mesh)
    tables = table(spec)
    key = base_key(seed)
    leaves = []
    for name, stack, sharding in names:
        entry = tables[STACKS[stack] if stack else 0][name][:4]
        layers = _stack_layers(spec, stack) if stack else None
        leaves.append(_maker(name, entry, layers, sharding)(key))
        jax.block_until_ready(leaves[-1])
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.lru_cache(maxsize=None)
def _maker(name: str, entry: tuple, layers, sharding):
    """The jitted maker of one leaf from the seed's key, in ``sharding``:
    layer 0's, or the stack of ``layers`` = (first, count).  Kept, so a
    process that makes the weights of many seeds traces each maker once."""
    if layers is None:
        return jax.jit(lambda k: _leaf(k, name, 0, entry),
                       out_shardings=sharding)
    first, n = layers
    return jax.jit(lambda k: jax.vmap(lambda i: _leaf(k, name, i, entry))(
        first + jnp.arange(n)), out_shardings=sharding)


def layer_f32(spec: dict):
    """``f(seed_key, layer) -> {name: float32 array}``, one layer's leaves
    as served, widened to float32, on the default device: the reference's
    weights.  The dense layers' and the MoE layers' leaves differ, so each
    kind compiles once."""
    _, dense, moe = table(spec)
    nd = dims(spec)["nd"]

    def make(entries):
        return jax.jit(lambda key, i: {n: _leaf(key, n, i, e[:4]).astype(F32)
                                       for n, e in entries.items()})
    f_dense, f_moe = make(dense), make(moe)
    return lambda key, i: (f_dense if i < nd else f_moe)(key, i)


def globals_f32(spec: dict, seed: int) -> dict:
    glob = table(spec)[0]
    f = jax.jit(lambda key: {n: _leaf(key, n, 0, e[:4]).astype(F32)
                             for n, e in glob.items()})
    return f(base_key(seed))
