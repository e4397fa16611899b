"""collective_ms: device time of the mesh train step's cross-chip
collectives, per round, on the first chip.

The collectives are the instructions of the train step the engine runs
(``system.train_step_text()``) whose opcode is one of ``OPCODES``: in
``fused_rs``, the reduce-scatter of the packed contribution row and the
all-gather of the aggregate row.  An asynchronous collective shows in the
trace as its ``-start`` and ``-done`` operations; each counts for its own
interval, not the time between them.  They are matched to the first
chip's operations by name and result shape, and the metric is the union
of their intervals over the window's rounds.
"""

import re

from bench.metrics.local_sgd_ms import _INSTRUCTION, result_shape, \
    scoped_seconds

OPCODES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
           "collective-permute")
_OPCODE = re.compile(r"([\w\-]+)\(")


def opcode(rest: str) -> str:
    """The opcode of an instruction's text after ``<name> = ``: the word
    before the first parenthesis after the result shape."""
    rest = re.sub(r"/\*.*?\*/", "", rest)
    depth = 0
    for i, ch in enumerate(rest):
        depth += {"(": 1, "[": 1, "{": 1, ")": -1, "]": -1, "}": -1}.get(
            ch, 0)
        if ch == " " and depth == 0:
            m = _OPCODE.match(rest[i:].lstrip())
            return m.group(1) if m else ""
    return ""


def collective_instructions(hlo_text: str) -> dict:
    """``{instruction: result shape}`` of the collectives of an HLO
    module's text, with their async ``-start`` and ``-done`` halves."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = opcode(m.group(2))
        base = re.sub(r"-(start|done)$", "", op)
        if base in OPCODES:
            out[m.group(1)] = result_shape(m.group(2))
    return out


def read(ctx):
    if ctx.trace is None:
        return None
    found = collective_instructions(ctx.system.train_step_text())
    seconds = scoped_seconds(ctx.trace.ops, found) if found else 0.0
    if seconds <= 0:
        return None
    return seconds / ctx.window.rounds * 1e3
