"""The benchmark's cost functions and peaks table, checked on the CPU:
the CNN's counted FLOPs against XLA's cost analysis, the aggregate
kernel's bytes against the program's packed layout, the copied byte
models against their originals, and an unknown device kind."""

import jax
import jax.numpy as jnp
import pytest

import bench.tests.tiny  # noqa: F401  (puts src/ on the path)
from bench.harness import cells, costs
from bench.harness.peaks import peaks_for

PAPER = cells.resolve("cnn70-paper").config


def test_cnn_params_match_the_program():
    from repro.models import cnn

    params = cnn.init_cnn(0)
    got = sum(x.size for x in jax.tree.leaves(params))
    assert costs.cnn_param_count(PAPER["model"]) == got == 1_663_370


def test_cnn_forward_flops_match_xla():
    """XLA also counts the bias adds, ReLUs and pooling compares, which
    ``costs`` leaves out: they are under 1% of the convolutions' and
    matmuls' FLOPs."""
    from bench.systems.fl_cnn import init_params
    from repro.models import cnn

    model = PAPER["model"]
    params = init_params(jax.random.key(0), model)
    x = jnp.zeros((4, model["image_hw"], model["image_hw"],
                   model["channels"]))
    ca = jax.jit(cnn.cnn_apply).lower(params, x).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    xla = ca["flops"] / 4
    counted = costs.cnn_forward_flops(model)
    assert counted <= xla <= counted * 1.01


def test_cnn_train_flops_are_forward_plus_two_backward_passes():
    macs = costs.cnn_layer_macs(PAPER["model"])
    fwd = costs.cnn_forward_flops(PAPER["model"])
    assert costs.cnn_train_flops(PAPER["model"]) == 3 * fwd - 2 * macs[
        "conv1"]


def test_aggregate_bytes_match_the_packed_layout():
    from repro.fl.packing import pack_spec
    from repro.models import cnn

    n = PAPER["population"]["n"]
    spec = pack_spec(jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((n,) + p.shape, p.dtype),
        cnn.init_cnn(0)))
    (group,) = spec.groups
    p = costs.cnn_param_count(PAPER["model"])
    assert group.total == p and group.dtype == jnp.float32
    bytes_ = costs.traffic_model(n, p, 4)["bytes_agg_only"]
    assert bytes_ == n * group.total * 4 + 4 * group.total
    assert group.padded >= group.total


def test_byte_models():
    """The aggregate reads the payload once; the fused kernel also writes
    the mixed deltas."""
    t = costs.traffic_model(70, 1000, 4)
    assert t["bytes_agg_only"] == 70 * 1000 * 4 + 4000
    assert t["bytes_fused"] == 2 * 70 * 1000 * 4 + 4000


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite").bf16_flops == 197e12
    assert peaks_for("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("cpu")
