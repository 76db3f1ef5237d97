"""The comparisons that decide ``correct``: each number is computed the
same way for the program and for the control, and held to the limit that
``limits/<workload>.json`` gives it."""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

LIMITS = pathlib.Path(__file__).resolve().parent / "limits"


def limits(workload: str) -> dict:
    return json.loads((LIMITS / f"{workload}.json").read_text())["limits"]


def rel(prog: float, ref: float) -> float:
    return abs(prog - ref) / abs(ref)


def worst_leaf(prog: dict, ref: dict, names=None) -> tuple[float, str]:
    """Worst leaf of |‖prog‖ - ‖ref‖| against the larger of the leaf's own
    reference norm and the median leaf's, and its name."""
    names = list(ref) if names is None else list(names)
    med = float(np.median([ref[n] for n in ref]))
    gap = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in names}
    worst = max(gap, key=gap.get)
    return gap[worst], worst


def moved(ref_grad: dict) -> list:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's norm."""
    med = float(np.median(list(ref_grad.values())))
    return [n for n, g in ref_grad.items() if g >= 1e-3 * med]


def verdict(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}})."""
    out = {n: {"value": float(v), "limit": float(lim[n])}
           for n, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in out.values())
    return ok, out


@jax.jit
def norms(named: dict) -> dict:
    """{name: array} -> {name: float32 norm}, one norm per layer for a
    leaf stacked over layers."""
    out = {}
    for n, x in named.items():
        x = x.astype(jnp.float32)
        if x.ndim >= 2 and n not in ("embed", "lm_head"):
            out[n] = jnp.sqrt(jnp.sum(x * x, tuple(range(1, x.ndim))))
        else:
            out[n] = jnp.sqrt(jnp.sum(x * x))
    return out


def leaf_norms(named: dict) -> dict:
    """{name: array} -> {leaf: float32 norm}, where a leaf stacked over
    layers gives one leaf per layer, named "<name>.<layer>"."""
    return flatten(norms(named))


def flatten(norms_by_name: dict) -> dict:
    out = {}
    for n, v in norms_by_name.items():
        v = np.asarray(v)
        if v.ndim:
            out.update({f"{n}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out[n] = float(v)
    return out
