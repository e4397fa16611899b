"""Each engine builds a round program once: a later ``execute`` with the
same quant (and, scanned, the same K) reuses the program the first built,
counts ``engine.program_cache`` ``hit``, compiles nothing, and gives
bitwise what fresh engines give.  The sequential drivers take each
round's plan row from host columns, with the values the device columns
held.  ``MeshEngine``'s half runs on a forced 4-device CPU mesh in a
subprocess (``helpers/engine_program_cache_mesh.py``)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import D2DNetwork, ServerConfig, make_round_fn
from repro.core.sparse import SparseAseq
from repro.fl import ExecutionConfig, RoundPlan, make_engine
from repro.fl import engine as engine_module
from repro.fl.packing import QuantSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, T, B, P = 6, 2, 3, 5
HIT = ("engine.program_cache", "hit")
MISS = ("engine.program_cache", "miss")


def quad_loss(params, batch):
    b, = batch
    return 0.5 * jnp.sum((params["x"] - b.mean(axis=0)) ** 2)


def _plan(seed, K, dropout=False):
    net = D2DNetwork(n=N, c=2, k_range=(2, 2), p_fail=0.1)
    plan = RoundPlan.connectivity_aware(net, ServerConfig(
        T=T, t_max=K, phi_max=0.5, seed=seed,
        eta=lambda t: 0.2 / (1 + 0.3 * t)))
    if dropout:
        plan = plan.with_dropout(0.3, np.random.default_rng(seed))
    return plan


def _batches(seed, K):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.standard_normal((N, T, B, P)), jnp.float32),)
            for _ in range(K)]


def _l2(params):
    return {"l2": float(jnp.sum(params["x"] ** 2))}


PARAMS0 = {"x": jnp.linspace(-1.0, 1.0, P, dtype=jnp.float32)}


@pytest.fixture
def builds(monkeypatch):
    """Count the builders' calls through the engine module's globals."""
    counts = {}
    for name in ("make_round_fn", "make_scanned_rounds"):
        real = getattr(engine_module, name)

        def counted(*a, _real=real, _name=name, **k):
            counts[_name] = counts.get(_name, 0) + 1
            return _real(*a, **k)

        monkeypatch.setattr(engine_module, name, counted)
    return counts


def _assert_same(run, fresh):
    (p, h), (q, g) = run, fresh
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(h.records) == len(g.records)
    for a, b in zip(h.records, g.records):
        assert (a.t, a.m, a.m_actual, a.psi_bound, a.d2s, a.d2d, a.eta,
                a.metrics) == (b.t, b.m, b.m_actual, b.psi_bound, b.d2s,
                               b.d2d, b.eta, b.metrics)
    assert h.ledger.d2s_per_round == g.ledger.d2s_per_round
    assert h.ledger.d2d_per_round == g.ledger.d2d_per_round


@pytest.mark.parametrize("backend", ["einsum", "aggregate"])
def test_second_execute_reuses_the_round_program(builds, backend):
    cfg = ExecutionConfig(backend=backend)
    plans = [_plan(1, 3), _plan(2, 3)]
    data = [_batches(1, 3), _batches(2, 3)]
    engine = make_engine(cfg, quad_loss)
    rec = spans.Recorder()
    with spans.recording(rec):
        first = engine.execute(plans[0], PARAMS0, data[0], eval_fn=_l2)
        second = engine.execute(plans[1], first[0], data[1], eval_fn=_l2)
    assert builds == {"make_round_fn": 1}
    assert rec.counters[MISS] == [0.0, 1] and rec.counters[HIT] == [0.0, 1]

    fresh = make_engine(cfg, quad_loss).execute(plans[0], PARAMS0, data[0],
                                                eval_fn=_l2)
    _assert_same(first, fresh)
    fresh = make_engine(cfg, quad_loss).execute(plans[1], fresh[0],
                                                data[1], eval_fn=_l2)
    _assert_same(second, fresh)
    assert builds == {"make_round_fn": 3}


def test_another_quant_builds_another_program(builds):
    engine = make_engine(ExecutionConfig(backend="einsum"), quad_loss)
    quant = QuantSpec(storage="int8", block=512)
    rec = spans.Recorder()
    with spans.recording(rec):
        params, _ = engine.execute(_plan(1, 2), PARAMS0, _batches(1, 2))
        params, _ = engine.execute(_plan(2, 2).with_quant(quant), params,
                                   _batches(2, 2))
        engine.execute(_plan(3, 2).with_quant(quant), params, _batches(3, 2))
    assert builds == {"make_round_fn": 2}
    assert rec.counters[MISS] == [0.0, 2] and rec.counters[HIT] == [0.0, 1]


def test_scanned_engine_builds_once_per_K(builds):
    cfg = ExecutionConfig(backend="einsum", scan=True)
    engine = make_engine(cfg, quad_loss)
    rec = spans.Recorder()
    with spans.recording(rec):
        a = engine.execute(_plan(1, 3), PARAMS0, _batches(1, 3))
        b = engine.execute(_plan(2, 3), a[0], _batches(2, 3))
        c = engine.execute(_plan(3, 4), b[0], _batches(3, 4))
    assert builds == {"make_scanned_rounds": 2}
    assert rec.counters[MISS] == [0.0, 2] and rec.counters[HIT] == [0.0, 1]
    fresh = PARAMS0
    for (seed, K), run in zip([(1, 3), (2, 3), (3, 4)], (a, b, c)):
        out = make_engine(cfg, quad_loss).execute(_plan(seed, K), fresh,
                                                  _batches(seed, K))
        _assert_same(run, out)
        fresh = out[0]


# each case its own K: jax keeps an eager op compiled at one shape for
# the whole process, so a shared K would let one case hide another
@pytest.mark.parametrize("backend,dropout,K", [
    ("einsum", False, 7), ("einsum", True, 8), ("aggregate", False, 9),
    ("aggregate", True, 10)])
def test_execute_at_a_new_K_compiles_nothing(backend, dropout, K):
    engine = make_engine(ExecutionConfig(backend=backend), quad_loss)
    params, _ = engine.execute(_plan(1, 3, dropout), PARAMS0, _batches(1, 3))
    plan, batches = _plan(2, K, dropout), _batches(2, K)
    rec = spans.Recorder()
    with spans.recording(rec):
        params, _ = engine.execute(plan, params, batches)
        jax.block_until_ready(params)
    # no trace, lowering or backend compile: the program cache alone
    # (and the engine's count of its cohort form)
    assert rec.counters == {HIT: [0.0, 1],
                            ("engine.cohort", "vmap"): [0.0, 1]}


@pytest.mark.parametrize("backend,dropout", [
    ("einsum", False), ("einsum", True), ("aggregate", True),
    ("sparse", False)])
def test_sequential_rows_are_the_device_columns_rows(backend, dropout):
    """The sequential driver's per-round arguments carry the values the
    device columns held: a loop over the same round program, indexing
    ``jnp.asarray`` columns, gives the same params bitwise."""
    plan, batches = _plan(4, 3, dropout), _batches(4, 3)
    engine = make_engine(ExecutionConfig(backend=backend), quad_loss)
    got, _ = engine.execute(plan, PARAMS0, batches)

    round_fn = make_round_fn(quad_loss, mixing_backend=engine.backend)
    if engine.backend == "sparse_aggregate":
        idx, w = SparseAseq.from_dense(plan.A_t).ell()
        A_seq = (jnp.asarray(idx), jnp.asarray(w))
    else:
        A_seq = jnp.asarray(plan.A_t, jnp.float32)
    tau_seq = jnp.asarray(plan.tau_t, jnp.float32)
    m_seq = jnp.asarray(plan.m_t, jnp.float32)
    eta_seq = jnp.asarray(plan.eta_t, jnp.float32)
    active_seq = jnp.asarray(plan.active_t, jnp.float32)
    want = PARAMS0
    for t in range(plan.n_rounds):
        A = ((A_seq[0][t], A_seq[1][t]) if isinstance(A_seq, tuple)
             else A_seq[t])
        extra = (active_seq[t],) if dropout else ()
        want, _ = round_fn(want, batches[t], A, tau_seq[t], m_seq[t],
                           eta_seq[t], *extra)
    np.testing.assert_array_equal(np.asarray(got["x"]),
                                  np.asarray(want["x"]))


def test_mesh_engine_builds_the_train_step_once():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tests", "helpers",
                      "engine_program_cache_mesh.py")],
        capture_output=True, text=True, timeout=400, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["builds_one_engine"] == 1
    assert out["counters"] == {"hit": 1, "miss": 1}
    assert out["second_execute_compiles"] == []
    assert out["builds_fresh_engines"] == 2
    assert out["bitwise_as_fresh_engines"] is True
