"""mfu: the whole round's share of the chips' bf16 peak: the FLOPs of
the clients' local SGD (forward and backward, from the model's shapes),
times rounds per second over the window, over chips x peak.  The CNN
is float32, but the TPU runs its matmuls and convolutions at default
precision as bf16 passes, so the bf16 peak is the fair denominator."""


def read(ctx):
    w = ctx.window
    rate = w.rounds / (w.t1 - w.t0)
    return 100.0 * ctx.system.train_flops_per_round() * rate \
        / (ctx.chips * ctx.peaks.bf16_flops)
