"""Public entry points of the Pallas kernels.

Each call compiles to Mosaic for the TPU.  ``interpret=True`` runs the
kernel body in Python instead (correctness checks on a CPU host); it is
never chosen from the backend, so a call without it on a host with no
TPU fails instead of silently timing the interpreter.

GQA/MQA KV enters ``flash_attention`` at its native (B, Hkv, S, D): the
kernel's KV block index maps resolve the group head, so no broadcast is
materialized and measured bytes match the model's accounting.
"""
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.mamba_scan import mamba_scan  # noqa: F401
from repro.kernels.nvdla_matmul import matmul  # noqa: F401
