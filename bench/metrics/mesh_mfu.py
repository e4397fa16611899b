"""mesh_mfu: the four clients' local-SGD FLOPs from the model's shapes
(``system.train_flops_per_round()``: 6 per matmul parameter and
attention's 12 S d a layer, per trained token; recomputation not
counted), times rounds per second over the traced window, over the
chips' bf16 peak (4 x 197 TFLOP/s).  The params are float32, but the
TPU runs the matmuls at default precision as bf16 passes.  The same
formula as ``mfu``, for the mesh cell."""

from bench.metrics import mfu


def read(ctx):
    return mfu.read(ctx)
