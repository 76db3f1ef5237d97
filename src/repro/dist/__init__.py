"""Distribution layer: mesh/runtime context, sharding rule engine, tensor-
parallel helpers, gradient compression, and pipeline parallelism.

Modules:
  context   — the process-global device mesh
  sharding  — logical-axis -> mesh-axis rule engine with divisibility guards
  tp        — tensor-parallel projection helper (closes a TP region)
  compress  — int8 block-quantized gradient all-reduce with error feedback
  pipeline  — GPipe-style pipeline parallelism over a 'stage' mesh axis
"""
from repro.dist import context  # noqa: F401
