"""round_ms: the whole window over the FL rounds completed in it; the
window ends when the last round's params are ready."""


def read(ctx):
    w = ctx.window
    return (w.t1 - w.t0) / w.rounds * 1e3
