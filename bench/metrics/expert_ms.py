"""expert_ms: device time of the routed experts in the MoE cell's round,
per round: the operations under ``jax.named_scope("moe_experts")``
(``models.moe.moe_apply``: the held slots' sort and gather, the grouped
matmuls and the combine), found and matched as ``moe_local_sgd_ms``
finds its scope; the grouped matmuls' ``ragged_dot`` kernels, which the
TPU compiler names without a path, count here by their name.  The router
and the shared experts have scopes of their own and are not counted."""

from bench.metrics.moe_local_sgd_ms import scope_ms

SCOPE = "moe_experts"


def read(ctx):
    return scope_ms(ctx, SCOPE)
