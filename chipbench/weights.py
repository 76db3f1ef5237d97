"""Random weights from the seed, made by the benchmark for the program and
for the reference alike.

The table of leaves is worked out from the configuration file, not from
the program: each leaf has a name (``embed``, ``attn.q``, ``moe.gate``,
...), a shape, the dtype it is served in and a scale.  Every value is a
function of the seed, the leaf's name and its layer alone, so the program
gets all layers stacked in one jitted call on the device, and the
reference regenerates any one layer bit for bit after the program's state
is gone.  ``program_params`` checks that the program's own parameter tree
has exactly these leaves, shapes and dtypes.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from chipbench.spec import dims

BF16, F32 = jnp.bfloat16, jnp.float32


def base_key(seed: int):
    """A key from a seed of up to 64 bits."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def table(spec: dict) -> tuple[dict, dict]:
    """(global leaves, per-layer leaves): name -> (shape, dtype, kind,
    scale).  ``kind`` is "normal" (scale times N(0, 1)) or "norm" (one
    plus scale times N(0, 1), a norm's gain)."""
    m = dims(spec)
    d, H, Hkv, hd, ff, V = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"], m["V"]

    def w(*shape):
        return shape, BF16, "normal", 1.0 / math.sqrt(shape[-2])

    glob = {"embed": ((V, d), BF16, "normal", 0.02),
            "final_norm": ((d,), F32, "norm", 0.1)}
    if not m["tied"]:
        glob["lm_head"] = w(d, V)
    layer = {"norm1": ((d,), F32, "norm", 0.1),
             "norm2": ((d,), F32, "norm", 0.1),
             "attn.q": w(d, H * hd), "attn.k": w(d, Hkv * hd),
             "attn.v": w(d, Hkv * hd), "attn.o": w(H * hd, d)}
    if m["E"]:
        E = m["E"]
        layer.update({"moe.router": ((d, E), F32, "normal",
                                     1.0 / math.sqrt(d)),
                      "moe.gate": w(E, d, ff), "moe.up": w(E, d, ff),
                      "moe.down": w(E, ff, d)})
    else:
        layer.update({"mlp.gate": w(d, ff), "mlp.up": w(d, ff),
                      "mlp.down": w(ff, d)})
    return glob, layer


def _leaf(key, name: str, layer, entry):
    shape, dtype, kind, scale = entry
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    x = scale * jax.random.normal(k, shape, F32)
    return (1.0 + x if kind == "norm" else x).astype(dtype)


def make_all(spec: dict, seed: int) -> dict:
    """Every leaf, by name, in its served dtype; per-layer leaves stacked
    on a leading layer axis.  One jitted call on the default device."""
    glob, layer = table(spec)
    L = dims(spec)["L"]

    @jax.jit
    def make(key):
        out = {n: _leaf(key, n, 0, e) for n, e in glob.items()}
        for n, e in layer.items():
            out[n] = jax.vmap(lambda i, n=n, e=e: _leaf(key, n, i, e))(
                jnp.arange(L))
        return out
    return make(base_key(seed))


def layer_f32(spec: dict):
    """``f(seed_key, layer) -> {name: float32 array}``, one layer's leaves
    as served, widened to float32: the reference's weights."""
    _, layer = table(spec)

    @jax.jit
    def f(key, i):
        return {n: _leaf(key, n, i, e).astype(F32) for n, e in layer.items()}
    return f


def globals_f32(spec: dict, seed: int) -> dict:
    glob, _ = table(spec)

    @jax.jit
    def f(key):
        return {n: _leaf(key, n, 0, e).astype(F32) for n, e in glob.items()}
    return f(base_key(seed))


def _path_name(path) -> tuple[str, bool]:
    """Program tree path -> (leaf name, stacked over layers)."""
    keys = [p.key for p in path]
    if keys[0] == "layers":
        return ".".join(keys[1:]), True
    return ".".join(keys), False


def program_layout(spec: dict, cfg):
    """The program's parameter tree, abstract, checked leaf by leaf against
    the table.  Returns (treedef, [(name, stacked)])."""
    from repro.models import transformer as T
    abstract = jax.eval_shape(lambda: T.init_params(cfg, jax.random.key(0))[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    glob, layer = table(spec)
    L = dims(spec)["L"]
    names, seen = [], set()
    for path, leaf in flat:
        name, stacked = _path_name(path)
        entry = (layer if stacked else glob).get(name)
        want = entry and ((L,) + entry[0] if stacked else entry[0])
        if entry is None or tuple(leaf.shape) != want or leaf.dtype != entry[1]:
            raise ValueError(
                f"the program's leaf {name} {leaf.shape} {leaf.dtype} is not "
                f"in the benchmark's table as {want} {entry and entry[1]}")
        names.append((name, stacked))
        seen.add(name)
    missing = (set(glob) | set(layer)) - seen
    if missing:
        raise ValueError(f"the program has no leaves {sorted(missing)}")
    return treedef, names


def program_params(spec: dict, cfg, seed: int):
    """The program's parameter tree filled with the benchmark's weights."""
    treedef, names = program_layout(spec, cfg)
    flat = make_all(spec, seed)
    return jax.tree_util.tree_unflatten(treedef, [flat[n] for n, _ in names])


def named(spec: dict, cfg, tree) -> dict:
    """A tree shaped like the program's parameters (the parameters, or the
    optimizer's moments) as {name: array}, the inverse of
    ``program_params``."""
    treedef, names = program_layout(spec, cfg)
    leaves = treedef.flatten_up_to(tree)
    return {n: x for (n, _), x in zip(names, leaves)}
