"""moe_mfu: the MoE cell's round as a share of the chip's bf16 peak: the
four clients' local-SGD FLOPs from the model's shapes
(``system.train_flops_per_round()``: 6 per matmul parameter a token
passes through, with the held experts at the share k x held / experts of
a token's top-k that falls on them, and attention's 6 S H (dn + dr + dv)
a layer; recomputation not counted), times rounds per second over the
traced window, over the chip's peak (197 TFLOP/s).  The params are
float32, but the TPU runs the matmuls at default precision as bf16
passes.  The same formula as ``mfu``."""

from bench.metrics import mfu


def read(ctx):
    return mfu.read(ctx)
