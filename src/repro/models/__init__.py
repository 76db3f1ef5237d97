# The ``jax.named_scope`` names that mark the layer boundaries inside the
# jitted steps.  They reach each HLO instruction's ``op_name``, and so the
# profiler's ``tf_op`` for every device operation; an operation belongs to
# the innermost of these in its path, read after stripping ``jvp(...)`` and
# ``transpose(...)`` (so a layer's backward counts as that layer).
#   embed      token and position embedding
#   layers     a layer stack's own work: the scan's slices of stacked
#              weights and cache, carry copies, norms, residual adds
#   attention  projections, RoPE, the attention core
#   kv_cache   writing and slicing the cache (inside ``attention``) and
#              building it after prefill
#   moe        router and experts, with ``route``, ``dispatch`` (capacity
#              buffers), ``experts`` (the expert FFNs) and ``combine``
#              (the weighted gather back) inside it
#   mlp        dense FFN
#   ssm        Mamba blocks
#   logits     final norm and unembedding
#   sample     the decode step's argmax
#   loss       cross-entropy, z-loss, MoE auxiliary losses
#   optimizer  gradient clipping and the AdamW update
SCOPES = ("embed", "layers", "attention", "kv_cache", "moe", "route",
          "dispatch", "experts", "combine", "mlp", "ssm", "logits", "sample",
          "loss", "optimizer")

from repro.models.transformer import (  # noqa: F401,E402
    decode_forward,
    init_cache,
    init_params,
    loss_fn,
    prefill_forward,
    train_forward,
)
