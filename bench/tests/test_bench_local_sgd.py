"""``local_sgd_ms``: the program's ``local_sgd`` scope found in the
round program's HLO and matched to a trace's operations by instruction
name and result shape (``data/hand_trace_scopes.pbtxt``), and the round
program the reader compiles is the one the engine ran."""

import os
import types

import jax
import pytest

from bench.tests.tiny import tiny_cell
from bench.harness import trace
from bench.harness.spans import Spans
from bench.metrics import local_sgd_ms

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# the round program's text as a TPU compile prints it, cut to the
# instructions that the hand trace runs
ROUND_HLO = '''HloModule jit_round_fn, is_scheduled=true

%body.3 (p: (s32[], f32[70,32], f32[70,10])) -> (s32[], f32[70,32], f32[70,10]) {
  %fusion.153 = f32[32,28,28,2240]{3,0,2,1:T(8,128)} fusion(bf16[5,70,32,28,28,1]{1,2,4,3,5,0:T(8,128)(2,1)} %get-tuple-element.341), kind=kOutput, calls=%fused_computation.153, metadata={op_name="jit(round_fn)/local_sgd/vmap()/while/body/closed_call/jvp()/conv_general_dilated" source_file="rounds.py" source_line=105}
  ROOT %tuple.7 = (s32[], f32[70,32], f32[70,10]) tuple(%a, %b, %c)
}

ENTRY %main.9 (p.0: f32[70,10]) -> (f32[10], f32[8,1665024]) {
  %broadcast_in_dim.79 = f32[70,5,5,1,32]{4,3,0,2,1:T(1,128)} broadcast(f32[5,5,32]{2,1,0:T(8,128)} %copy_bitcast_fusion.1), dimensions={1,2,4}, metadata={op_name="jit(round_fn)/local_sgd/vmap()/broadcast_in_dim" source_file="rounds.py" source_line=129}
  %while.5 = (s32[]{:T(128)}, f32[70,32]{1,0:T(8,128)}, /*index=2*/f32[70,10]{1,0:T(8,128)}) while(%tuple.1), condition=%cond.2, body=%body.3, metadata={op_name="jit(round_fn)/local_sgd/vmap()/while" source_file="rounds.py" source_line=115}
  ROOT %aggregate.1 = f32[8,1665024]{1,0:T(8,128)} custom-call(%pad.9, %pad.10), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/mix/jit(aggregate_grouped)/jit(aggregate)/aggregate/pallas_call" source_file="ops.py" source_line=235}
}
'''


def _summary():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "hand_trace_scopes.pbtxt")) as f:
        text = f.read()
    return trace.reduce_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def _context(summary, rounds=2):
    return types.SimpleNamespace(trace=summary, system=None,
                                 window=types.SimpleNamespace(rounds=rounds))


@pytest.mark.parametrize("text, shape", [
    ("f32[8,72]{1,0:T(8,128)} custom-call(f32[8,72]{1,0} %p)", "f32[8,72]"),
    ("(s32[]{:T(128)}, /*index=1*/f32[70,32]{1,0}) while((s32[]) %t)",
     "(s32[],f32[70,32])"),
    ("bf16[5,5,70,32]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%x)",
     "bf16[5,5,70,32]"),
    ("f32[] constant(0)", "f32[]"),
], ids=["array", "tuple", "tiled", "scalar"])
def test_result_shape_drops_layouts_and_comments(text, shape):
    assert local_sgd_ms.result_shape(text) == shape


def test_scoped_instructions_of_the_round_program():
    assert local_sgd_ms.scoped_instructions(ROUND_HLO, "local_sgd") == {
        "fusion.153": "f32[32,28,28,2240]",
        "broadcast_in_dim.79": "f32[70,5,5,1,32]",
        "while.5": "(s32[],f32[70,32],f32[70,10])"}
    assert list(local_sgd_ms.scoped_instructions(ROUND_HLO, "mix")) == [
        "aggregate.1"]


def test_local_sgd_ms_on_the_hand_trace(monkeypatch):
    monkeypatch.setattr(local_sgd_ms, "round_program_text",
                        lambda system: ROUND_HLO)
    # 5-8 and 8-60 (fusion.153 nested), 80-95: not the aggregate, nor
    # the eval's op that shares a name with another shape
    assert local_sgd_ms.read(_context(_summary())) == pytest.approx(
        (3 + 52 + 15) / 2 * 1e-3)


def test_local_sgd_ms_is_silent_without_the_scope(monkeypatch):
    """A program without the scope (an older checkout under this
    benchmark) yields nothing, and no error; so does an untraced run."""
    unscoped = ROUND_HLO.replace("/local_sgd/", "/")
    monkeypatch.setattr(local_sgd_ms, "round_program_text",
                        lambda system: unscoped)
    assert local_sgd_ms.read(_context(_summary())) is None
    assert local_sgd_ms.read(_context(None)) is None


def test_reader_compiles_the_round_program_the_engine_ran(monkeypatch):
    """The reader's round program has the engine's instructions, name
    for name and shape for shape, and names its local SGD."""
    from repro.fl import engine

    ran = {}
    real = engine.make_round_fn

    def make_round_fn(*args, **kwargs):
        fn = real(*args, **kwargs)

        def first_call(*a):
            ran.setdefault("lowered", fn.lower(*a))
            return fn(*a)
        return first_call

    monkeypatch.setattr(engine, "make_round_fn", make_round_fn)
    cell = tiny_cell()
    system = cell.system().System(cell, 2 ** 31 + 3, Spans(), jax.devices())
    system.setup()
    system.release()

    def instructions(text):
        return {m.group(1): local_sgd_ms.result_shape(m.group(2))
                for m in map(local_sgd_ms._INSTRUCTION.match,
                             text.splitlines()) if m}

    engine_text = ran["lowered"].compile().as_text()
    reader_text = local_sgd_ms.round_program_text(system)
    assert instructions(reader_text) == instructions(engine_text)
    scoped = local_sgd_ms.scoped_instructions(reader_text, "local_sgd")
    assert any(name.startswith("while") for name in scoped)
