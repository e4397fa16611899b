"""mesh_local_sgd_ms: device time of the clients' local SGD in the mesh
train step, per round, on the first chip.

The program names it with ``jax.named_scope("local_sgd")``
(``fl.distributed.make_train_step``).  As for ``local_sgd_ms``, the
trace's operations carry only their HLO instruction, so the scope is found
in the train step the engine runs, compiled again at the cell's shapes
(``system.train_step_text()``), and its ``/local_sgd/`` instructions are
matched to the first chip's operations by name and result shape.  A
program without the scope yields nothing.
"""

from bench.metrics.local_sgd_ms import scoped_instructions, scoped_seconds

SCOPE = "local_sgd"


def read(ctx):
    if ctx.trace is None:
        return None
    scoped = scoped_instructions(ctx.system.train_step_text(), SCOPE)
    seconds = scoped_seconds(ctx.trace.ops, scoped) if scoped else 0.0
    if seconds <= 0:
        return None
    return seconds / ctx.window.rounds * 1e3
