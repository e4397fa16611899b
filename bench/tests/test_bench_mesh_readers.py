"""The mesh cell's readers on a hand-written four-chip trace
(``data/hand_trace_mesh.pbtxt``): ``mesh_local_sgd_ms`` finds the train
step's ``local_sgd`` scope and ``collective_ms`` its cross-chip
collectives (async halves each for its own interval) by name and result
shape, so the eval program's like-named collective does not count;
``mesh_mfu`` is the local-SGD FLOPs a round over four chips' peak."""

import os
import types

import pytest

from bench.harness import trace
from bench.harness.peaks import PEAKS
from bench.metrics import collective_ms, mesh_local_sgd_ms, mesh_mfu

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# the mesh train step's text as a TPU compile prints it, cut to the
# instructions that the hand trace runs
STEP_HLO = '''HloModule jit_train_step, is_scheduled=true

ENTRY %main.9 (p.0: f32[822202368]) -> f32[822202368] {
  %while.241 = (s32[]{:T(128)}, f32[4,8,2048]{2,1,0:T(8,128)}) while((s32[]{:T(128)}, f32[4,8,2048]{2,1,0:T(8,128)}) %tuple.12), condition=%cond.3, body=%body.4, metadata={op_name="jit(train_step)/local_sgd/vmap()/while" source_file="distributed.py" source_line=476}
  %fusion.9 = bf16[8,1,2048,2048]{3,2,1,0:T(8,128)(2,1)} fusion(f32[1,2048,2048]{2,1,0:T(8,128)} %param.3), kind=kOutput, calls=%fused_computation.9, metadata={op_name="jit(train_step)/local_sgd/vmap()/while/body/closed_call/dot_general" source_file="attention.py" source_line=88}
  %all-reduce = f32[822202368]{0:T(1024)} all-reduce(f32[822202368]{0:T(1024)} %bitcast.80), channel_id=3, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_42.46.clone, backend_config={"flag_configs":[]}
  %all-gather-start.2 = (f32[205550592]{0:T(1024)}, f32[822202368]{0:T(1024)}) all-gather-start(f32[205550592]{0:T(1024)} %dynamic-slice.122), channel_id=2, replica_groups=[1,4]<=[4], dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(train_step)/mix/sharding_constraint"}
  %fusion.7 = f32[16]{0:T(128)} fusion(f32[4]{0:T(128)} %param.7), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(train_step)/mix/mul"}
  %all-gather-done.2 = f32[822202368]{0:T(1024)} all-gather-done((f32[205550592]{0:T(1024)}, f32[822202368]{0:T(1024)}) %all-gather-start.2), metadata={op_name="jit(train_step)/mix/sharding_constraint"}
  ROOT %tuple.30 = (f32[822202368]{0:T(1024)}) tuple(%all-gather-done.2)
}
'''


def _summary():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "hand_trace_mesh.pbtxt")) as f:
        text = f.read()
    return trace.reduce_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)))


def _context(summary, rounds=2, flops=1e12, seconds=0.1):
    system = types.SimpleNamespace(train_step_text=lambda: STEP_HLO,
                                   train_flops_per_round=lambda: flops)
    window = types.SimpleNamespace(rounds=rounds, t0=0.0, t1=seconds)
    return types.SimpleNamespace(trace=summary, system=system, window=window,
                                 chips=4, peaks=PEAKS["TPU v5 lite"])


def test_hand_trace_has_four_chips():
    s = _summary()
    assert s.n_devices == 4
    # chip 0 busy 5-50, 52-64, 66-67, 70-90, 91-95 us; the others 5-50
    # and 70-90
    assert s.busy_s == pytest.approx((45 + 12 + 1 + 20 + 4 + 3 * 65) / 4
                                     * 1e-6)


@pytest.mark.parametrize("text, op", [
    ("f32[822202368]{0:T(1024)} all-reduce(%bitcast.80), channel_id=3",
     "all-reduce"),
    ("(f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %x), dimensions={0}",
     "all-gather-start"),
    ("(s32[]{:T(128)}, /*index=1*/f32[70,32]{1,0}) while((s32[]) %t)",
     "while"),
    ("f32[] constant(0)", "constant"),
], ids=["sync", "async", "tuple", "scalar"])
def test_opcode_after_the_result_shape(text, op):
    assert collective_ms.opcode(text) == op


def test_collective_instructions_of_the_step():
    assert collective_ms.collective_instructions(STEP_HLO) == {
        "all-reduce": "f32[822202368]",
        "all-gather-start.2": "(f32[205550592],f32[822202368])",
        "all-gather-done.2": "f32[822202368]"}


def test_collective_ms_on_the_hand_trace():
    # all-reduce 52-58 and 91-95, all-gather start 58-59 and done 60-64;
    # not the mix's fusion, nor the eval's scalar all-reduce
    assert collective_ms.read(_context(_summary())) == pytest.approx(
        (6 + 1 + 4 + 4) / 2 * 1e-3)


def test_mesh_local_sgd_ms_on_the_hand_trace():
    # while.241 5-50 (fusion.9 inside it) and 70-90, on chip 0
    assert mesh_local_sgd_ms.read(_context(_summary())) == pytest.approx(
        (45 + 20) / 2 * 1e-3)


def test_readers_are_silent_without_what_they_read():
    """An untraced run, and a step without the scope or collectives (an
    older checkout under this benchmark), yield nothing and no error."""
    assert collective_ms.read(_context(None)) is None
    assert mesh_local_sgd_ms.read(_context(None)) is None
    ctx = _context(_summary())
    bare = "\n".join(line for line in STEP_HLO.splitlines()
                     if "all-" not in line).replace("/local_sgd/", "/")
    ctx.system.train_step_text = lambda: bare
    assert collective_ms.read(ctx) is None
    assert mesh_local_sgd_ms.read(ctx) is None


def test_mesh_mfu_is_the_round_flops_over_four_chips():
    ctx = _context(_summary(), rounds=2, flops=3.94e12, seconds=1.0)
    assert mesh_mfu.read(ctx) == pytest.approx(
        100 * 3.94e12 * 2 / (4 * 197e12))
