"""Process-global distribution context: the active device mesh.

The launchers (``repro.launch.*``) install a mesh before tracing; model
code reads it through the accessors here rather than through its call
signatures.

Everything defaults to "no mesh" so single-device tests and benchmarks
need no setup.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

_STATE = {"mesh": None}


def set_mesh(mesh) -> None:
    """Install (or clear, with ``None``) the active device mesh."""
    _STATE["mesh"] = mesh


def get_mesh():
    return _STATE["mesh"]


def mesh_axis_size(name: str) -> int:
    """Size of a mesh axis; 1 when no mesh or the axis is absent."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return 1
    try:
        return int(dict(mesh.shape).get(name, 1))
    except TypeError:
        return 1


def dp_axes() -> Optional[Union[str, Tuple[str, ...]]]:
    """The data-parallel mesh axes (>1) in ('pod', 'data') order.

    Returns a bare name, a tuple, or None — directly usable as a
    PartitionSpec entry or a psum/pmean axis_name."""
    axes = tuple(a for a in ("pod", "data") if mesh_axis_size(a) > 1)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes
