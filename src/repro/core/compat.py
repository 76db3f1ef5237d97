"""``shard_map`` with JAX's replication checking off: the EP MoE path and
the pipeline psum explicitly."""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
