"""input_ms: host time drawing the clients' batches (the sampler the
benchmark hands to FederatedServer, span ``input``), per round."""


def read(ctx):
    w = ctx.window
    return ctx.spans.total_s("input", w.t0, w.t1) / w.rounds * 1e3
