"""The comparison that decides ``correct`` for a training cell.

The numbers; those that the cell's ``bench/limits/<cell>.json`` gives a
limit are compared, the others are only read (``bench/calibrate.py``):

- ``loss_gap``: the largest relative gap, over the first rounds, between
  the loss the program reports after a round and the reference's loss
  after the same round;
- ``update_gap``: the first round's update ``x1 - x0`` (the step as the
  optimizer takes it), by the worst leaf;
- ``change_gap``: the change ``xK - x0`` after the first K rounds, by the
  worst leaf;
- ``change_err``: the same change, by the worst leaf's norm of the
  difference ``|prog - ref|`` over the same denominator.  The gaps of
  norms cannot see a change that keeps each leaf's norm but turns it,
  such as a round whose D2D mix is left out;
- ``comm_mismatch``: rounds whose History comm columns (m, d2s, d2d)
  differ from what the reference counts from the plan's columns; exact.

A leaf's gap is ``| |prog| - |ref| |`` of its Frobenius norms, over the
larger of the reference's norm of that leaf and of the median leaf.
Leaves whose reference update is below a thousandth of the median
leaf's move by round-off alone and are left out.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Callable, Dict, List, Sequence

import numpy as np

from .cells import BENCH_DIR

SKIP_BELOW = 1e-3


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def load_limits(cell_name: str) -> Dict[str, float]:
    path = os.path.join(BENCH_DIR, "limits", cell_name + ".json")
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def _leaves(tree) -> Dict[str, np.ndarray]:
    import jax

    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _diff(a, b) -> Dict[str, np.ndarray]:
    la, lb = _leaves(a), _leaves(b)
    return {k: la[k] - lb[k] for k in la}


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep: Sequence[str]) -> float:
    """Worst leaf's gap of norms (module docstring) over ``keep``."""
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    median = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[k])) - norms[k])
               / max(norms[k], median, 1e-30) for k in keep)


def leaf_err(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep: Sequence[str]) -> float:
    """Worst leaf's norm of the difference, over the same denominator as
    ``leaf_gap``."""
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    median = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(prog[k] - ref[k]))
               / max(norms[k], median, 1e-30) for k in keep)


def moving_leaves(ref_update: Dict[str, np.ndarray]) -> List[str]:
    """Leaves whose reference update is not nought to rounding."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref_update.items()}
    median = float(np.median(list(norms.values())))
    return sorted(k for k, v in norms.items() if v >= SKIP_BELOW * median)


def training_numbers(x0, params: Sequence, losses: Sequence[float],
                     ref_params: Sequence, ref_losses: Sequence[float]
                     ) -> Dict[str, float]:
    """``loss_gap``, ``update_gap`` and ``change_gap`` of a run against
    the reference, both started from ``x0``."""
    ref_update = _diff(ref_params[0], x0)
    keep = moving_leaves(ref_update)
    loss_gap = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(losses, ref_losses))
    if not all(math.isfinite(x) for x in losses):
        loss_gap = float("inf")
    change, ref_change = _diff(params[-1], x0), _diff(ref_params[-1], x0)
    return {
        "loss_gap": float(loss_gap),
        "update_gap": leaf_gap(_diff(params[0], x0), ref_update, keep),
        "change_gap": leaf_gap(change, ref_change, keep),
        "change_err": leaf_err(change, ref_change, keep),
    }


def comm_mismatches(plans: Sequence, histories: Sequence,
                    comm: Callable) -> int:
    """Rounds whose recorded (m, d2s, d2d) differ from ``comm(A, tau,
    active)`` computed from the executed plan's columns."""
    bad = 0
    for plan, history in zip(plans, histories):
        for t, rec in enumerate(history.records):
            want = comm(plan.A_t[t], plan.tau_t[t], plan.active_t[t])
            if (rec.m_actual, rec.d2s, rec.d2d) != tuple(want):
                bad += 1
    return bad


def comm_check(plans, histories, comm, limits) -> Check:
    return Check("comm_mismatch",
                 float(comm_mismatches(plans, histories, comm)),
                 limits["comm_mismatch"])
