"""plan_ms: host time building each segment's RoundPlan (span ``plan``),
per round."""


def read(ctx):
    w = ctx.window
    return ctx.spans.total_s("plan", w.t0, w.t1) / w.rounds * 1e3
