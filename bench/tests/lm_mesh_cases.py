"""``stablelm2-mesh4`` cut to a size the CPU runs in seconds, and the runs
its tests read, made in one process on four host CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.lm_mesh_cases

The same system, traffic, reference and limits as the cell, with two
layers of width 256 (4 heads of 64), vocab 1024 and 64-token sequences.
Prints one JSON object: each case's result line, the spans that
``MeshEngine.execute`` recorded in the clean run, and whether the train
step the readers compile is the one the engine ran.
"""

import copy
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELL = "stablelm2-mesh4"
SEED = 2 ** 31 + 17


def tiny_lm_cell():
    from bench.harness import cells

    cell = cells.resolve(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(num_hidden_layers=2, hidden_size=256,
                        intermediate_size=704, num_attention_heads=4,
                        num_key_value_heads=4, vocab_size=1024)
    cfg["population"].update(train_tokens=8192)
    cfg["training"].update(T=2, eta=0.05)
    traffic = dict(cell.traffic, seq_len=64, segment_rounds=3)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def _step_wrapper(wrap):
    """Patch the engine's ``make_train_step`` so each step runs through
    ``wrap(step)``; returns the undo."""
    from repro.fl import engine

    real = engine.make_train_step
    engine.make_train_step = lambda *a, **k: wrap(real(*a, **k))
    return lambda: setattr(engine, "make_train_step", real)


def _frozen():
    return _step_wrapper(lambda step: lambda params, *a, **k: params)


def _half_batch():
    def wrap(step):
        def half(params, tokens, *a, **k):
            return step(params, tokens[..., : (tokens.shape[-1] - 1) // 2
                                       + 1], *a, **k)
        return half
    return _step_wrapper(wrap)


def _no_mixing():
    import jax.numpy as jnp

    def wrap(step):
        def unmixed(params, tokens, A, *a, **k):
            return step(params, tokens, jnp.eye(A.shape[0], dtype=A.dtype),
                        *a, **k)
        return unmixed
    return _step_wrapper(wrap)


def _no_carry_over():
    from repro.core.server import FederatedServer

    real = FederatedServer.run

    def run(self, *a, **k):
        start = self.params
        history = real(self, *a, **k)
        self.params = start
        return history

    FederatedServer.run = run
    return lambda: setattr(FederatedServer, "run", real)


FAULTS = {"frozen": _frozen, "half_batch": _half_batch,
          "no_mixing": _no_mixing, "no_carry_over": _no_carry_over}


def _run(cell, recorder=None):
    import jax

    from bench.harness import runner
    from bench.harness.peaks import PEAKS
    from repro import spans

    args = (cell, SEED, 0.0, False, jax.devices(), PEAKS["TPU v5 lite"],
            time.perf_counter())
    if recorder is None:
        return runner.run_cell(*args)
    with spans.recording(recorder):
        return runner.run_cell(*args)


def _capture_step_text(ran):
    """Patch the engine so the first train step it runs records its
    compiled text in ``ran["text"]``; returns the undo."""
    def wrap(step):
        def first_call(*a, **k):
            ran.setdefault("text", step.lower(*a, **k).compile().as_text())
            return step(*a, **k)
        return first_call
    return _step_wrapper(wrap)


def _instructions(text):
    from bench.metrics.local_sgd_ms import _INSTRUCTION, result_shape

    return {m.group(1): result_shape(m.group(2))
            for m in map(_INSTRUCTION.match, text.splitlines()) if m}


def main() -> int:
    import jax

    from repro import spans

    if len(jax.devices()) < 4:
        print("lm_mesh_cases: needs four devices", file=sys.stderr)
        return 2
    from bench.harness.spans import Spans

    cell = tiny_lm_cell()
    recorder, ran = spans.Recorder(), {}
    undo = _capture_step_text(ran)
    try:
        out = {"clean": _run(cell, recorder)}
    finally:
        undo()
    out["spans"] = [[r.name, r.parent, r.round] for r in recorder.spans
                    if r.name.startswith("engine.")]
    # the train step the readers compile is the one the engine ran
    reader = cell.system().System(cell, SEED, Spans(), jax.devices())
    out["reader_step_is_engine_step"] = _instructions(
        ran["text"]) == _instructions(reader.train_step_text())
    for name, plant in FAULTS.items():
        undo = plant()
        try:
            out[name] = _run(cell)
        finally:
            undo()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
