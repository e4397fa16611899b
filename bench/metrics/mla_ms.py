"""mla_ms: device time of latent attention in the MoE cell's round, per
round: the operations under ``jax.named_scope("mla")``
(``models.mla.mla_full``), forward, recomputation and backward, found
and matched as ``moe_local_sgd_ms`` finds its scope."""

from bench.metrics.moe_local_sgd_ms import scope_ms

SCOPE = "mla"


def read(ctx):
    return scope_ms(ctx, SCOPE)
