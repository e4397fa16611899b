"""moe_local_sgd_ms: device time of the clients' local SGD in the MoE
cell's round, per round, on the chip.

The program names it with ``jax.named_scope("local_sgd")`` around each
client's T steps (``core.rounds.sequential_aggregate`` in the cell's
sequential cohort form).  As for ``local_sgd_ms``, the trace's operations
carry only their HLO instruction, so the scope is found in the program
the clients train in, compiled again at the cell's shapes
(``system.train_step_text()``), and its instructions are matched to the
chip's operations by name and result shape.  A program without the scope
yields nothing.

A scope entered inside a differentiated function appears in the name
path under the transform's name when no scope encloses it there: the
dense first layer's attention is ``.../jvp(mla)/dot_general`` forward
and ``.../transpose(jvp(mla))/...`` backward, where the scanned layers'
read ``.../mla/...``.  So a scope counts as a component of the path,
bare or inside a transform's parentheses.

The TPU compiler lowers ``jax.lax.ragged_dot`` to kernels whose
``op_name`` is ``ragged-dot-none`` or ``ragged-dot-metadata``, with no
name path.  In the clients' program ``ragged_dot`` runs only in the held
experts' grouped matmuls (``models.moe._experts_ragged``), which lie
inside ``local_sgd`` and ``moe_experts``: those two scopes count them.
"""

import re

from bench.metrics.local_sgd_ms import (_INSTRUCTION, _OP_NAME,
                                        result_shape, scoped_seconds)

SCOPE = "local_sgd"

# the ragged_dot kernels' op_name, and the scopes they lie in
_GROUPED = re.compile(r"^ragged-dot-")
GROUPED_SCOPES = ("local_sgd", "moe_experts")


def scoped_instructions(hlo_text: str, scope: str) -> dict:
    """``{instruction: result shape}`` of the instructions of an HLO
    module's text whose ``op_name`` holds ``scope`` as a component of its
    name path, with the ``ragged_dot`` kernels in ``GROUPED_SCOPES``
    (module docstring)."""
    key = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[)/]|$)")
    grouped = scope in GROUPED_SCOPES
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        op_name = _OP_NAME.search(line)
        if m and op_name and (key.search(op_name.group(1)) or (
                grouped and _GROUPED.match(op_name.group(1)))):
            out[m.group(1)] = result_shape(m.group(2))
    return out


def scope_ms(ctx, scope: str):
    """Device ms a round under ``/<scope>/`` of the system's train step,
    or None where the program has no such scope or the trace none of its
    operations."""
    if ctx.trace is None:
        return None
    scoped = scoped_instructions(ctx.system.train_step_text(), scope)
    seconds = scoped_seconds(ctx.trace.ops, scoped) if scoped else 0.0
    if seconds <= 0:
        return None
    return seconds / ctx.window.rounds * 1e3


def read(ctx):
    return scope_ms(ctx, SCOPE)
