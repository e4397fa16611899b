"""A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds:
the same system, traffic, reference and limits, with a small CNN and
eight clients in two clusters, sampled below n after round 0 (so the
D2D mix shows in the aggregate)."""

import copy
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import cells  # noqa: E402

CELL = "cnn70-paper"


def tiny_cell(name: str = CELL):
    cell = cells.resolve(name)
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(image_hw=8, conv1=4, conv2=8, fc_hidden=16)
    cfg["population"].update(n=8, clusters=2, k_min=2, k_max=3,
                             train_samples=640, test_samples=96)
    cfg["training"].update(T=2, batch=4, phi_max=2.0, eta=0.002)
    traffic = dict(cell.traffic, segment_rounds=3)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)
