"""``stablelm2-mesh4`` on the CPU: a tiny cut of the cell through
``runner.run_cell`` on four host CPU devices, in one subprocess
(``lm_mesh_cases``) whose outcomes the tests read: the cut is correct,
each planted fault is not, ``MeshEngine.execute`` opens its spans with
the right rounds, and the readers compile the train step the engine ran.
In this process: the comparison from leaf norms reads the same numbers as
the harness's, and set-up refuses a program whose block is not the
published one."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench.harness.compare import training_numbers
from bench.tests.lm_mesh_cases import FAULTS, ROOT, tiny_lm_cell
from bench.systems import fl_lm_mesh


@pytest.fixture(scope="module")
def cases():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-m", "bench.tests.lm_mesh_cases"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tiny_cut_is_correct(cases):
    result = cases["clean"]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == fl_lm_mesh.CHECK_ROUNDS
    assert result["failed"] == 0
    assert set(result["checks"]) == {"loss_gap", "update_gap", "change_gap",
                                     "change_err", "comm_mismatch"}
    assert {"round_ms", "setup_s"} <= set(result["metrics"])
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(cases, fault):
    result = cases[fault]
    assert result["correct"] is False, result["checks"]
    assert result["failed"] == result["attempted"]


def test_mesh_engine_opens_its_spans(cases):
    """Each segment: ``engine.prepare``, then per round ``engine.dispatch``
    and ``engine.eval`` with the plan's round, all under ``server.run``."""
    got = cases["spans"]
    assert all(parent == "server.run" for _, parent, _ in got)
    rounds = fl_lm_mesh.CHECK_ROUNDS
    segment = [["engine.prepare", None]] + [
        [name, t] for t in range(rounds)
        for name in ("engine.dispatch", "engine.eval")]
    # set-up's segment, then the window's one
    assert [[name, t] for name, _, t in got] == segment * 2


def test_reader_compiles_the_step_the_engine_ran(cases):
    assert cases["reader_step_is_engine_step"] is True


def _tree(rng, shapes, scale=1.0):
    return {k: (scale * rng.standard_normal(s)).astype(np.float32)
            for k, s in shapes.items()}


@pytest.mark.parametrize("frozen", [False, True], ids=["moving", "frozen"])
def test_leaf_numbers_read_what_training_numbers_reads(frozen):
    rng = np.random.default_rng(5)
    shapes = {"a": (7, 3), "b": (11,), "c": (2, 5, 4), "d": (300,)}
    x0 = _tree(rng, shapes)
    step = lambda s: {k: x0[k] + _tree(rng, shapes, s)[k]  # noqa: E731
                      for k in shapes}
    ref_first, ref_last = step(1e-2), step(3e-2)
    first, last = ((x0, x0) if frozen else
                   (step(1e-2), {k: ref_last[k] + _tree(rng, shapes, 1e-3)[k]
                                 for k in shapes}))
    losses, ref_losses = [2.0, 1.9, 1.8], [2.0, 1.91, 1.79]
    want = training_numbers(x0, [first, last], losses,
                            [ref_first, ref_last], ref_losses)
    got = fl_lm_mesh.leaf_numbers(x0, first, last, losses, ref_first,
                                  ref_last, ref_losses)
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-5, abs=1e-9), k


def _parent_config(monkeypatch):
    """The parent's stablelm-1.6b: no q/k/v biases, eps 1e-6."""
    import dataclasses

    from repro import configs

    real = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda name: dataclasses.replace(
                            real(name), qkv_bias=False, norm_eps=1e-6))


def _no_shift_leaf(monkeypatch):
    """A layer norm whose params are a bare scale."""
    import jax.numpy as jnp

    from repro.models import blocks, model

    bare = lambda d, kind="rms", dtype=jnp.float32: jnp.ones(  # noqa: E731
        (d,), dtype)
    monkeypatch.setattr(blocks, "norm_init", bare)
    monkeypatch.setattr(model, "norm_init", bare)


@pytest.mark.parametrize("plant", [_parent_config, _no_shift_leaf],
                         ids=["config", "params"])
def test_setup_refuses_a_block_that_is_not_published(plant, monkeypatch):
    """A program whose stablelm-1.6b lacks the q/k/v biases or the
    LayerNorm shifts fails at once, before any data or weights."""
    plant(monkeypatch)
    cell = tiny_lm_cell()
    with pytest.raises(ValueError, match="published"):
        cell.system().System(cell, 1, None, jax.devices() * 4)


def test_train_flops_from_shapes():
    cell = tiny_lm_cell()
    m = cell.config["model"]
    d, ff, L, V = 256, 704, 2, 1024
    S = cell.traffic["seq_len"]
    per_token = 6 * (L * (4 * d * d + 3 * d * ff) + d * V) + 12 * S * d * L
    assert fl_lm_mesh.train_flops_per_token(m, S) == per_token
