"""setup_s: seconds from the start of the process to the start of the
window: imports, data, weights, compilation and the first rounds that
the reference follows."""


def read(ctx):
    return ctx.setup_s
