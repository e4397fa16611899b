"""Operations and bytes the algorithm needs, from shapes alone.

``traffic_model`` is a copy of the byte model in
``benchmarks/mixing_kernel.py``; the FLOP counts of the client
models are worked out here from their layer shapes.  Counted are the
operations the algorithm requires: a multiply-add is two FLOPs, padding
and recomputation are not counted, nor are elementwise operations.
"""

from __future__ import annotations

from typing import Dict


def traffic_model(n: int, p: int, itemsize: int) -> dict:
    """Bytes moved per round for each mixing schedule (payload terms
    only): ``n`` client rows of ``p`` values of ``itemsize`` bytes, and
    the fp32 aggregate row."""
    npB = n * p * itemsize
    pB = p * 4
    return dict(
        bytes_two_pass=3 * npB + pB,
        bytes_fused=2 * npB + pB,
        bytes_agg_only=npB + pB,
    )


def _conv_macs(hw: int, c_in: int, c_out: int, k: int) -> int:
    """Multiply-adds of a stride-1 ``SAME`` k x k convolution over an
    hw x hw image, counting only taps that fall inside the image."""
    taps = 0
    half = k // 2
    for y in range(hw):
        for x in range(hw):
            ny = min(y + half, hw - 1) - max(y - half, 0) + 1
            nx = min(x + half, hw - 1) - max(x - half, 0) + 1
            taps += ny * nx
    return taps * c_in * c_out


def cnn_layer_macs(model: Dict) -> Dict[str, int]:
    """Forward multiply-adds per sample of each weight layer of the
    McMahan et al. CNN described by a configuration's ``model`` block."""
    hw, c = model["image_hw"], model["channels"]
    c1, c2, k = model["conv1"], model["conv2"], model["kernel"]
    flat = (hw // 4) * (hw // 4) * c2
    return {
        "conv1": _conv_macs(hw, c, c1, k),
        "conv2": _conv_macs(hw // 2, c1, c2, k),
        "fc1": flat * model["fc_hidden"],
        "fc2": model["fc_hidden"] * model["n_classes"],
    }


def cnn_forward_flops(model: Dict) -> int:
    """Forward FLOPs per sample (weight layers only)."""
    return 2 * sum(cnn_layer_macs(model).values())


def cnn_train_flops(model: Dict) -> int:
    """Forward and backward FLOPs per sample of one SGD step: forward,
    the weight gradient of every layer, and the input gradient of every
    layer but the first (the image needs none)."""
    macs = cnn_layer_macs(model)
    fwd = sum(macs.values())
    return 2 * (fwd + fwd + (fwd - macs["conv1"]))


def cnn_param_count(model: Dict) -> int:
    hw, c = model["image_hw"], model["channels"]
    c1, c2, k = model["conv1"], model["conv2"], model["kernel"]
    flat = (hw // 4) * (hw // 4) * c2
    h, o = model["fc_hidden"], model["n_classes"]
    return (k * k * c * c1 + c1 + k * k * c1 * c2 + c2 + flat * h + h
            + h * o + o)
