"""The trace reduction, on a trace written by hand in the profiler's
format (``data/hand_trace.pbtxt``)."""

import os

import pytest

import bench.tests.tiny  # noqa: F401  (puts src/ on the path)
from bench.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _hand():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "hand_trace.pbtxt")) as f:
        text = f.read()
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_union_clips_and_merges():
    total, merged = trace.union_ns([(5, 15), (10, 20), (30, 40), (50, 60)],
                                   8, 55)
    assert merged == [(8, 20), (30, 40), (50, 55)]
    assert total == 12 + 10 + 5


def test_hand_trace_reduces():
    s = trace.reduce_profile(_hand())
    us = 1e-6
    assert s.window_s == pytest.approx(100 * us)
    assert s.n_devices == 2
    # device 0: 10-30 and 40-60 us; device 1: 0-50 us
    assert s.busy_s == pytest.approx((40 + 50) / 2 * us)
    assert s.op_s == pytest.approx({"fusion.1": 20 * us,
                                    "aggregate.1": 10 * us,
                                    "fusion.2": 15 * us})
    assert s.kernel_s("aggregate") == (pytest.approx(10 * us), 1)
    assert s.kernel_s("fusion") == (0, 0)      # not a Mosaic kernel
    assert s.kernel_s("no_such_kernel") == (0, 0)
    # idle on device 0: 0-10 and 30-40 (host), 60-100 (input, 55-90)
    assert s.idle_by_span == pytest.approx({"host": 20 * us,
                                            "input": 40 * us})
    b = s.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["fusion.1", "fusion.2",
                                               "aggregate.1"]
    assert b["idle_gaps"][0] == ["input", pytest.approx(40 * us)]


def test_trace_without_window_or_device_is_refused():
    from jax.profiler import ProfileData

    host_only = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
            'events { metadata_id: 1 duration_ps: 1000 } } event_metadata '
            '{ key: 1 value { id: 1 name: "bench.window" } } }'))
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce_profile(host_only)
    no_window = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 1 name: "/device:TPU:0" }'))
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_profile(no_window)
