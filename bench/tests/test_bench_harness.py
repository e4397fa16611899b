"""The harness on the CPU at a tiny size: cells resolve by name, a run's
last line has the contract's keys, the entry point refuses the CPU, and
a run whose timed path is broken underneath, or the control put in the
program's place, comes out not correct."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench.tests.tiny import ROOT, tiny_cell
from bench.harness import cells, runner
from bench.harness.compare import load_limits
from bench.harness.peaks import PEAKS

V5E = PEAKS["TPU v5 lite"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cell, seed=2 ** 31 + 11):
    return runner.run_cell(cell, seed, 0.05, False, jax.devices(), V5E,
                           time.perf_counter())


def test_every_cell_resolves_by_name():
    spec = cells.load_spec()
    assert spec["paths"] == ["bench"]
    for w in spec["workloads"]:
        cell = cells.resolve(w["name"], spec)
        assert hasattr(cell.system(), "System")
        assert hasattr(cell.reference(), "run_rounds")
        assert load_limits(cell.name)
        assert {m.name for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.reader().read), m.name


def test_benchmark_json_names_and_bounds():
    spec = cells.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_result_line_has_the_contract_keys():
    result = _run(tiny_cell())
    keys = list(result)
    assert keys[:5] == list(runner.RESULT_KEYS)
    assert keys[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert {"round_ms", "setup_s"} <= set(result["metrics"])
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)


def _frozen(monkeypatch):
    from repro.fl import engine

    def make_round_fn(*a, **k):
        return lambda params, *rest: (params, None)

    monkeypatch.setattr(engine, "make_round_fn", make_round_fn)


def _half_batch(monkeypatch):
    from repro.core import rounds

    real = rounds.local_sgd

    def local_sgd(loss_fn, params, batches, eta):
        half = jax.tree.map(lambda b: b[:, : b.shape[1] // 2], batches)
        return real(loss_fn, params, half, eta)

    monkeypatch.setattr(rounds, "local_sgd", local_sgd)


def _no_mixing(monkeypatch):
    from repro.kernels.mixing import ops

    real = ops.aggregate_grouped

    def aggregate_grouped(A, tau, m, bufs, **kw):
        return real(jnp.eye(A.shape[0], dtype=A.dtype), tau, m, bufs, **kw)

    monkeypatch.setattr(ops, "aggregate_grouped", aggregate_grouped)


def _no_carry_over(monkeypatch):
    """Every segment starts again from the params the server was built
    with: the window's segments do not carry the params over."""
    from repro.core.server import FederatedServer

    real = FederatedServer.run

    def run(self, *a, **k):
        start = self.params
        history = real(self, *a, **k)
        self.params = start
        return history

    monkeypatch.setattr(FederatedServer, "run", run)


@pytest.mark.parametrize(
    "fault", [_frozen, _half_batch, _no_mixing, _no_carry_over],
    ids=["frozen", "half_batch", "no_mixing", "no_carry_over"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    result = _run(tiny_cell())
    assert result["correct"] is False, result["checks"]
    assert result["failed"] == result["attempted"]


def test_control_is_not_correct():
    """The reference in bfloat16, in the program's place, fails one of
    the cell's numbers on every seed tried."""
    from bench import calibrate

    cell = tiny_cell()
    limits = load_limits(cell.name)
    for seed in (3, 2 ** 31 + 5):
        got = dict(calibrate.readings(cell, seed, jax.devices(), faults=()))
        assert all(got["program"][k] <= v for k, v in limits.items())
        assert any(got["control"][k] > v for k, v in limits.items()
                   if k in got["control"]), got["control"]


def _entry(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cnn70-paper",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_refuses_the_cpu():
    r = _entry(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_entry_alone_fails(tmp_path):
    """With only BENCHMARK.json and bench/ beside it the run fails,
    printing no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _entry(tmp_path)
    assert r.returncode != 0
    assert "{" not in r.stdout
