"""local_sgd_ms: device time of the clients' local SGD, per round.

The program names it with ``jax.named_scope("local_sgd")``
(``core.rounds.client_deltas``).  A TPU trace's operations carry no name
path, only their HLO instruction (``%while.5 = (s32[], ...) while(...)``),
so the scope is found in the round program itself: compiled here as the
engine compiles it (the same loss, backend and shapes), its instructions
whose ``op_name`` metadata holds ``/local_sgd/`` are matched to the
trace's operations by name and result shape (shape, so that a
like-named instruction of another program, such as an eager eval op,
does not count).  The metric is the union of the matched operations'
intervals on the first chip, over the window's rounds.  A program
without the scope yields nothing.
"""

import re

SCOPE = "local_sgd"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def result_shape(rest: str) -> str:
    """The result shape at the start of an instruction's text after
    ``<name> = ``, without layouts or comments: ``f32[8,72]`` or
    ``(s32[], f32[70,32])``."""
    rest = re.sub(r"/\*.*?\*/", "", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rest = rest[:i + 1]
                break
    else:
        rest = rest.split(" ", 1)[0]
    return re.sub(r"\s+", "", re.sub(r"\{[^{}]*\}", "", rest))


def scoped_instructions(hlo_text: str, scope: str) -> dict:
    """``{instruction: result shape}`` of the instructions of an HLO
    module's text whose ``op_name`` holds ``/<scope>/``."""
    key = "/" + scope + "/"
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        op_name = _OP_NAME.search(line)
        if m and op_name and key in op_name.group(1):
            out[m.group(1)] = result_shape(m.group(2))
    return out


def scoped_seconds(ops, scoped: dict) -> float:
    """Seconds of the union of the intervals of ``ops`` (the trace
    reduction's first-chip operations) that run a ``scoped``
    instruction."""
    from bench.harness.trace import union_ns

    hits = []
    for o in ops:
        shape = scoped.get(o.name)
        if shape is None or " = " not in o.text:
            continue
        if result_shape(o.text.split(" = ", 1)[1]) == shape:
            hits.append((o.start_ns, o.start_ns + o.dur_ns))
    return union_ns(hits, float("-inf"), float("inf"))[0] * 1e-9


def round_program_text(system) -> str:
    """The round program that ``bench.systems.fl_cnn`` drives, compiled
    for the default device: ``repro.launch.train``'s round as the
    system's ``FederatedServer`` builds it, at the cell's shapes."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from bench.systems.fl_cnn import init_params
    from repro.core.rounds import make_round_fn
    from repro.fl import ExecutionConfig, resolve_backend
    from repro.models import cnn

    model, pop, tr = (system.cfg["model"], system.cfg["population"],
                      system.cfg["training"])
    execution = ExecutionConfig(backend=system.traffic["backend"])
    round_fn = make_round_fn(
        partial(cnn.l2_regularized_loss, cnn.cnn_apply, mu=model["l2_mu"]),
        mixing_backend=resolve_backend(execution), chunk=execution.chunk,
        interpret=execution.interpret)
    n, T, B, hw = pop["n"], tr["T"], tr["batch"], model["image_hw"]
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(partial(init_params, model=model),
                            jax.random.key(0))
    batches = (shape((n, T, B, hw, hw, model["channels"]), f32),
               shape((n, T, B), jnp.int32))
    return round_fn.lower(params, batches, shape((n, n), f32),
                          shape((n,), f32), shape((), f32),
                          shape((), f32)).compile().as_text()


def read(ctx):
    if ctx.trace is None:
        return None
    scoped = scoped_instructions(round_program_text(ctx.system), SCOPE)
    seconds = scoped_seconds(ctx.trace.ops, scoped) if scoped else 0.0
    if seconds <= 0:
        return None
    return seconds / ctx.window.rounds * 1e3
