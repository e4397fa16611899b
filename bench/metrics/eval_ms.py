"""eval_ms: host time in the eval function the benchmark supplies (test
accuracy and test loss, span ``eval``), per round."""


def read(ctx):
    w = ctx.window
    if ctx.spans.count("eval", w.t0, w.t1) == 0:
        return None
    return ctx.spans.total_s("eval", w.t0, w.t1) / w.rounds * 1e3
