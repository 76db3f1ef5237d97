"""Mesh builders.

Functions (not module-level constants) so importing this module never touches
jax device state.  The launchers that run build their mesh from the local
devices (``make_host_mesh``).  The 16x16 production mesh is only lowered and
compiled: the dry-run launchers set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing jax
(see launch/dryrun.py) so it can be built on a CPU-only host.
"""
from __future__ import annotations

from typing import Optional

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod (v5e); 2 pods for the multi-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"the production mesh needs {n} devices, have {len(devices)}. "
            f"It is for lowering and compiling only: repro.launch.dryrun "
            f"fakes 512 CPU devices for it.  To run on the chips this "
            f"process holds, build the mesh with make_host_mesh")
    import numpy as np
    dev_array = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(dev_array, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests / examples)."""
    import numpy as np
    n = data * model
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return jax.sharding.Mesh(np.asarray(devices).reshape(data, model),
                             ("data", "model"))
