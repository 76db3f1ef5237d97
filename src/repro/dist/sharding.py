"""Logical-axis sharding rule engine.

Parameters and activations carry *logical* axis names (``"d_ff"``,
``"heads_x_dim"``, ``"kv_seq"``...); a ``Rules`` table maps each logical axis
to a mesh axis (or a tuple of mesh axes, or None).  ``spec_for`` applies the
table with the safety guards that make the whole (arch x shape x mesh) sweep
lowerable:

  * a mesh axis of size 1 never shards anything,
  * a dimension is only sharded when its size is divisible by the mesh-axis
    product,
  * a mesh axis is used at most once per spec (first logical axis wins),
  * a spec with nothing sharded collapses to the replicated ``P()``.

``rules_for`` derives the per-cell table: data-parallel batch sharding when
the batch divides, sequence-parallel fallback when it cannot (long-context
decode), TP over heads with the MQA head_dim fallback, and expert/FFN
sharding over 'model'.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple, Union

from repro.core.config import ModelConfig, ShapeConfig

Entry = Union[str, Tuple[str, ...], None]


def _mesh_shape(mesh) -> Dict[str, int]:
    if mesh is None:
        return {}
    return {k: int(v) for k, v in dict(mesh.shape).items()}


@dataclass
class Rules:
    table: Dict[str, Entry]
    mesh: Any = None

    # -- spec construction ---------------------------------------------------
    def spec_for(self, axes: Sequence[Optional[str]],
                 shape: Sequence[int]):
        """PartitionSpec for a tensor with the given logical axes."""
        from jax.sharding import PartitionSpec as P
        ms = _mesh_shape(self.mesh)
        used: set = set()
        entries = []
        sharded = False
        for i, ax in enumerate(axes):
            entry = self.table.get(ax) if ax is not None else None
            if entry is None:
                entries.append(None)
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            size = math.prod(ms.get(n, 1) for n in names)
            dim = shape[i] if i < len(shape) else 0
            if size <= 1 or any(n in used for n in names) \
                    or dim % size != 0:
                entries.append(None)
                continue
            used.update(names)
            entries.append(entry)
            sharded = True
        if not sharded:
            return P()
        return P(*entries)

    def tree_shardings(self, axes_tree, value_tree):
        """NamedShardings for a pytree whose axes-tree leaves are tuples of
        logical names (the ``Leaf.axes`` convention)."""
        import jax
        from jax.sharding import NamedSharding

        def _axes_leaf(x):
            return x is None or (isinstance(x, tuple) and all(
                e is None or isinstance(e, str) for e in x))

        ax_flat, treedef = jax.tree_util.tree_flatten(
            axes_tree, is_leaf=_axes_leaf)
        val_flat = treedef.flatten_up_to(value_tree)
        out = [NamedSharding(self.mesh,
                             self.spec_for(a or (), tuple(v.shape)))
               for a, v in zip(ax_flat, val_flat)]
        return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# rule derivation


def default_rules(mesh) -> Rules:
    """Generic table: DP batch, TP everything wide, no sequence parallelism."""
    ms = _mesh_shape(mesh)
    dp = tuple(a for a in ("pod", "data") if ms.get(a, 1) > 1)
    batch: Entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    return Rules(table={
        "batch": batch,
        "vocab": "model",
        "d_model": None,
        "d_ff": "model",
        "d_inner": "model",
        "heads_x_dim": "model",
        "kv_heads_x_dim": "model",
        "kv_heads": "model",
        "head_dim": None,
        "experts": "model",
        "kv_seq": None,
        "layers": None,
        "kv_lora": None,
        "ssm_heads": None,
    }, mesh=mesh)


def rules_for(cfg: ModelConfig, shape: ShapeConfig, mesh) -> Rules:
    """Per-cell rule table (divisibility-guarded; see module docstring)."""
    ms = _mesh_shape(mesh)
    model = ms.get("model", 1)
    data = ms.get("data", 1)
    dp_names = tuple(a for a in ("pod", "data") if ms.get(a, 1) > 1)
    dp = math.prod(ms.get(a, 1) for a in dp_names) if dp_names else 1
    B, S = shape.global_batch, shape.seq_len
    hd = cfg.resolved_head_dim

    table: Dict[str, Entry] = {
        "d_model": None, "layers": None, "kv_lora": None, "ssm_heads": None,
    }

    # batch: DP when it divides; otherwise replicated and SP takes over
    if dp_names and dp > 1 and B % dp == 0:
        table["batch"] = dp_names if len(dp_names) > 1 else dp_names[0]
    else:
        table["batch"] = None

    # sequence parallelism over 'data' when the batch could not use it
    # (single-sequence long-context decode — the KV cache is the big tensor)
    if table["batch"] is None and data > 1 and S % data == 0:
        table["kv_seq"] = "data"
    else:
        table["kv_seq"] = None

    # tensor parallelism over 'model'
    def tp(n: int) -> Entry:
        return "model" if model > 1 and n % model == 0 else None

    table["heads_x_dim"] = tp(cfg.n_heads)
    table["kv_heads_x_dim"] = tp(cfg.n_kv_heads)
    table["kv_heads"] = table["kv_heads_x_dim"]
    # MQA/GQA fallback: too few KV heads for the model axis -> shard the
    # head_dim of the cache instead so long-context decode still distributes
    table["head_dim"] = tp(hd) if table["kv_heads"] is None else None
    table["d_ff"] = tp(cfg.d_ff)
    table["vocab"] = tp(cfg.vocab)
    if cfg.ssm is not None:
        table["d_inner"] = tp(cfg.ssm.expand * cfg.d_model)
    else:
        table["d_inner"] = None
    if cfg.moe is not None:
        table["experts"] = tp(cfg.moe.n_experts)
    else:
        table["experts"] = None
    return Rules(table=table, mesh=mesh)


# ---------------------------------------------------------------------------
# active-rules global (installed by the launchers, read by ``constrain``)

_ACTIVE: Dict[str, Optional[Rules]] = {"rules": None}


def set_active_rules(rules: Optional[Rules]) -> None:
    _ACTIVE["rules"] = rules


def active_rules() -> Optional[Rules]:
    return _ACTIVE["rules"]


def constrain(x, axes: Sequence[Optional[str]]):
    """Sharding-constrain ``x`` per the active rules; identity when no rules
    or no real mesh are installed (single-device tests)."""
    rules = _ACTIVE["rules"]
    if rules is None or rules.mesh is None \
            or not hasattr(rules.mesh, "devices"):
        return x
    import jax
    from jax.sharding import NamedSharding
    spec = rules.spec_for(axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
