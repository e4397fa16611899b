"""Declarative RoundPlan + Engine API (ISSUE 4 tentpole).

Covers: legacy-kwarg shims (DeprecationWarning + History equivalence),
the backend-selection matrix in ``resolve_backend``, straggler masks
(``active_t``) -- all-ones bitwise-identical to the unmasked paths,
dropped clients matching a dense oracle that zeros their deltas and
renormalizes -- plan constructors, JSON round-trips, and the
plan-driven ``FederatedServer.run``.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (D2DNetwork, FederatedServer, ServerConfig,
                        client_deltas, global_update, make_round_fn,
                        make_scanned_rounds, mix_deltas)
from repro.core.rounds import mask_clients
from repro.core.sparse import ell_from_dense
from repro.fl import ExecutionConfig, RoundPlan, make_engine, plan_rows, \
    resolve_backend
from repro.kernels.mixing.ops import combine_weights

jax.config.update("jax_enable_x64", False)


def quad_loss(params, batch):
    x = params["x"]
    b, = batch
    return 0.5 * jnp.sum((x - b.mean(axis=0)) ** 2)


def _net_cfg(n=12, c=2, t_max=5, seed=3, **kw):
    net = D2DNetwork(n=n, c=c, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=3, t_max=t_max, phi_max=0.3, seed=seed,
                       eta=lambda t: 0.2 / (1 + 0.3 * t), **kw)
    return net, cfg


def _sampler(n, p, T=3, B=2):
    targets = np.random.default_rng(11).standard_normal((n, p)) \
        .astype(np.float32)

    def sampler(r, t):
        samp = targets[:, None, None, :] \
            + 0.05 * r.standard_normal((n, T, B, p))
        return (jnp.asarray(samp, jnp.float32),)

    return sampler


def _server(execution=None, p=4, eval_key="gap", **kw):
    net, cfg = _net_cfg()
    server = FederatedServer(net, quad_loss, {"x": jnp.zeros(p)},
                             _sampler(net.n, p), cfg, algorithm="semidec",
                             execution=execution, **kw)
    hist = server.run(eval_fn=lambda prm: {
        eval_key: float(jnp.sum(prm["x"] ** 2))})
    return server, hist


def _round_setup(seed=9, n=6, p=5, T=3, B=2):
    rng = np.random.default_rng(seed)
    batches = (jnp.asarray(rng.standard_normal((n, T, B, p)), jnp.float32),)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    m = jnp.float32(max(1.0, float(tau.sum())))
    return batches, A, tau, m, jnp.float32(0.1), {"x": jnp.zeros(p)}


# ---------------------------------------------------------------------------
# legacy kwargs: DeprecationWarning + History equivalence
# ---------------------------------------------------------------------------

def test_legacy_kwargs_warn_and_match_execution_config():
    with pytest.warns(DeprecationWarning, match="mixing_backend"):
        s_old, h_old = _server(mixing_backend="fused", scan_rounds=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        s_new, h_new = _server(
            execution=ExecutionConfig(backend="fused", scan=True))
    assert s_old.effective_backend == s_new.effective_backend == "aggregate"
    np.testing.assert_array_equal(np.asarray(s_old.params["x"]),
                                  np.asarray(s_new.params["x"]))
    assert len(h_old.records) == len(h_new.records)
    for a, b in zip(h_old.records, h_new.records):
        assert (a.t, a.m, a.m_actual, a.d2s, a.d2d, a.eta, a.psi_bound,
                a.metrics) == \
            (b.t, b.m, b.m_actual, b.d2s, b.d2d, b.eta, b.psi_bound,
             b.metrics)
    np.testing.assert_array_equal(h_old.ledger.cumulative_cost(),
                                  h_new.ledger.cumulative_cost())


def test_execution_config_and_legacy_kwargs_conflict():
    net, cfg = _net_cfg()
    with pytest.raises(ValueError, match="not both"):
        FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                        _sampler(net.n, 4), cfg,
                        execution=ExecutionConfig(),
                        mixing_backend="fused")
    # the jit kwarg must not be silently dropped when it contradicts
    # the ExecutionConfig
    with pytest.raises(ValueError, match="jit"):
        FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                        _sampler(net.n, 4), cfg, jit=False,
                        execution=ExecutionConfig())
    # agreeing values are fine
    FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                    _sampler(net.n, 4), cfg, jit=True,
                    execution=ExecutionConfig())


@pytest.mark.parametrize("ecfg,effective", [
    (ExecutionConfig(backend="fused"), "aggregate"),
    (ExecutionConfig(backend="fused", record_mixed=True), "fused"),
    (ExecutionConfig(backend="einsum"), "einsum"),
    (ExecutionConfig(backend="aggregate"), "aggregate"),
])
def test_resolve_backend_matrix(ecfg, effective):
    assert resolve_backend(ecfg) == effective


def test_resolve_backend_rejects_invalid_combinations():
    with pytest.raises(ValueError, match="mixing_backend"):
        resolve_backend(ExecutionConfig(backend="nope"))
    with pytest.raises(ValueError, match="record_mixed"):
        resolve_backend(ExecutionConfig(backend="aggregate",
                                        record_mixed=True))
    with pytest.raises(ValueError, match="model_cfg"):
        resolve_backend(ExecutionConfig(backend="fused", mesh=object()))
    with pytest.raises(ValueError, match="mesh mixing"):
        resolve_backend(ExecutionConfig(backend="aggregate", mesh=object(),
                                        model_cfg=object()))


# ---------------------------------------------------------------------------
# straggler masks: all-ones == unmasked, bitwise, on every backend
# ---------------------------------------------------------------------------

def _A_for(backend, A):
    """The ELL backends take ``A`` as the neighbor-list pair."""
    if backend.startswith("sparse"):
        return tuple(jnp.asarray(a) for a in ell_from_dense(np.asarray(A)))
    return A


ALL_BACKENDS = ["einsum", "fused", "aggregate", "sparse", "sparse_aggregate"]


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_round_fn_all_ones_active_is_bitwise_noop(backend):
    batches, A, tau, m, eta, params = _round_setup()
    A = _A_for(backend, A)
    fn = make_round_fn(quad_loss, mixing_backend=backend, chunk=256)
    p0, mx0 = fn(params, batches, A, tau, m, eta)
    p1, mx1 = fn(params, batches, A, tau, m, eta,
                 jnp.ones_like(tau))
    np.testing.assert_array_equal(np.asarray(p0["x"]), np.asarray(p1["x"]))
    if mx0 is not None:
        np.testing.assert_array_equal(np.asarray(mx0["x"]),
                                      np.asarray(mx1["x"]))


def test_combine_weights_all_ones_active_is_bitwise_noop():
    _, A, tau, m, _, _ = _round_setup()
    w0 = combine_weights(A, tau, m)
    w1 = combine_weights(A, tau, m, jnp.ones_like(tau))
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))


# ---------------------------------------------------------------------------
# dropout: every backend matches the dense oracle (zero the dropped
# client's delta, remove its upload, renormalize by the effective count)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_dropout_round_matches_dense_oracle(backend):
    batches, A, tau, _, eta, params = _round_setup()
    # drop a sampled client and an unsampled D2D neighbor
    active = jnp.asarray([1, 1, 0, 1, 0, 1], jnp.float32)
    m_eff = jnp.float32(max(1.0, float((tau * active).sum())))

    deltas = client_deltas(quad_loss, params, batches, eta)
    mixed = mix_deltas(A, mask_clients(deltas, active))
    want = global_update(params, mixed, tau * active, m_eff)

    fn = make_round_fn(quad_loss, mixing_backend=backend, chunk=256)
    got, got_mixed = fn(params, batches, _A_for(backend, A), tau, m_eff,
                        eta, active)
    np.testing.assert_allclose(np.asarray(got["x"]), np.asarray(want["x"]),
                               rtol=1e-5, atol=1e-6)
    if got_mixed is not None:
        np.testing.assert_allclose(np.asarray(got_mixed["x"]),
                                   np.asarray(mixed["x"]),
                                   rtol=1e-5, atol=1e-6)


def test_scanned_rounds_with_dropout_bitwise_vs_sequential():
    rng = np.random.default_rng(21)
    n, p, T, B, K = 5, 4, 3, 2, 4
    batches, As, taus, ms, actives = [], [], [], [], []
    targets = rng.standard_normal((n, p))
    for _ in range(K):
        samp = targets[:, None, None, :] \
            + 0.05 * rng.standard_normal((n, T, B, p))
        batches.append((jnp.asarray(samp, jnp.float32),))
        As.append(jnp.asarray(rng.random((n, n)), jnp.float32))
        tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
        act = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
        taus.append(tau)
        actives.append(act)
        ms.append(jnp.float32(max(1.0, float((tau * act).sum()))))
    etas = [jnp.float32(0.2 / (1 + t)) for t in range(K)]
    params = {"x": jnp.zeros(p)}

    round_fn = make_round_fn(quad_loss)
    seq, prm = [], params
    for t in range(K):
        prm, _ = round_fn(prm, batches[t], As[t], taus[t], ms[t], etas[t],
                          actives[t])
        seq.append(np.asarray(prm["x"]))

    scanned = make_scanned_rounds(quad_loss, K)
    batches_seq = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    final, params_seq = scanned(params, batches_seq, jnp.stack(As),
                                jnp.stack(taus), jnp.stack(ms),
                                jnp.stack(etas), jnp.stack(actives))
    np.testing.assert_array_equal(np.asarray(final["x"]), seq[-1])
    for t in range(K):
        np.testing.assert_array_equal(np.asarray(params_seq["x"][t]), seq[t])


@pytest.mark.parametrize("backend", ["einsum", "fused", "aggregate"])
@pytest.mark.parametrize("scan", [False, True])
def test_server_dropout_plan_consistent_across_backends(backend, scan):
    """A dropout plan executes to the same trajectory on every backend
    (einsum is the oracle), sequential and scanned."""
    net, cfg = _net_cfg()
    plan = RoundPlan.connectivity_aware(net, cfg).with_dropout(
        0.4, np.random.default_rng(5))
    assert plan.has_dropout

    def run(ecfg):
        server = FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                                 _sampler(net.n, 4), cfg,
                                 execution=ecfg)
        hist = server.run(plan=plan)
        return server, hist

    s_ref, h_ref = run(ExecutionConfig(backend="einsum"))
    s_got, h_got = run(ExecutionConfig(backend=backend, scan=scan))
    np.testing.assert_allclose(np.asarray(s_got.params["x"]),
                               np.asarray(s_ref.params["x"]),
                               rtol=1e-5, atol=1e-6)
    # effective uploads drive the ledger: fewer than the dense plan's
    dense_d2s = (plan.tau_t.sum(axis=1)).astype(int)
    for t, rec in enumerate(h_got.records):
        assert rec.d2s == int(plan.d2s_t[t]) <= dense_d2s[t]
    np.testing.assert_array_equal(h_got.ledger.cumulative_cost(),
                                  h_ref.ledger.cumulative_cost())


# ---------------------------------------------------------------------------
# zero-survivor round: when every client drops, the round is a no-op --
# the eq.-4 divisor clamps to 1, the aggregate is exactly zero, params
# carry forward bitwise, and the History records m_actual=0.  Pinned on
# every mixing backend, sequential and scanned.  (The mesh analogue is
# test_mesh_train_step_dropped_client_is_identity; the semi-async
# analogue is the deadline-shortfall test in test_stream_engine.py.)
# ---------------------------------------------------------------------------

def _zero_survivor_plan():
    net, cfg = _net_cfg(t_max=3)
    plan = RoundPlan.connectivity_aware(net, cfg)
    active = np.ones_like(plan.active_t)
    active[1, :] = 0.0                      # everybody drops in round 1
    plan = plan.with_active(active)
    assert int(plan.m_actual_t[1]) == 0 and float(plan.m_t[1]) == 1.0
    return net, cfg, plan


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("scan", [False, True])
def test_zero_survivor_round_is_noop_every_backend(backend, scan):
    net, cfg, plan = _zero_survivor_plan()
    # keep the materializing legs: unrecorded, 'fused' and 'sparse' would
    # run as their aggregate-only upgrades
    execution = ExecutionConfig(backend=backend, scan=scan,
                                record_mixed=backend in ("fused", "sparse"))

    def run(p):
        server = FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                                 _sampler(net.n, 4), cfg,
                                 execution=execution)
        hist = server.run(plan=p)
        return server.params, hist

    params_full, hist = run(plan)
    # the dead round is recorded, finite, and free
    rec = hist.records[1]
    assert rec.m_actual == 0 and rec.d2s == 0
    assert np.isfinite(np.asarray(params_full["x"])).all()
    # params across the dead round are bitwise those of the truncated
    # run: rounds 0..2 with round 1 dead == rounds {0, 2} never happen,
    # so compare against stopping right before the dead round
    params_head, _ = run(plan[:1])
    params_resumed, _ = run(plan[:2])
    np.testing.assert_array_equal(np.asarray(params_resumed["x"]),
                                  np.asarray(params_head["x"]))


def test_zero_survivor_round_backends_agree_bitwise():
    """All backends produce the identical trajectory through a dead
    round (the clamp-to-1 divisor is shared, not per-backend)."""
    net, cfg, plan = _zero_survivor_plan()

    def run(backend):
        server = FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                                 _sampler(net.n, 4), cfg,
                                 execution=ExecutionConfig(backend=backend))
        server.run(plan=plan)
        return np.asarray(server.params["x"])

    ref = run("einsum")
    for backend in ALL_BACKENDS[1:]:
        np.testing.assert_allclose(run(backend), ref,
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# RoundPlan: constructors, transforms, serialization
# ---------------------------------------------------------------------------

def test_plan_constructors_shapes_and_semantics():
    net, cfg = _net_cfg(t_max=4)
    plan = RoundPlan.connectivity_aware(net, cfg)
    K, n = cfg.t_max, net.n
    assert (plan.n_rounds, plan.n_clients) == (K, n)
    assert plan.A_t.shape == (K, n, n) and plan.tau_t.shape == (K, n)
    assert not plan.has_dropout
    # equal-neighbor matrices are column-stochastic
    np.testing.assert_allclose(plan.A_t.sum(axis=1), 1.0, atol=1e-5)
    assert (plan.m_actual_t == plan.tau_t.sum(axis=1)).all()
    assert (plan.d2s_t == plan.m_actual_t).all()
    assert np.isfinite(plan.psi_bound_t).all()

    cfg_f = ServerConfig(T=3, t_max=4, m_fixed=6, seed=1)
    fed = RoundPlan.fedavg(net, cfg_f)
    assert (fed.A_t == np.eye(n, dtype=np.float32)).all()
    assert (fed.d2d_t == 0).all() and (fed.m_planned_t == 6).all()
    assert np.isnan(fed.psi_bound_t).all()

    col = RoundPlan.colrel(net, cfg_f)
    assert (col.d2d_t > 0).all() and (col.m_planned_t == 6).all()

    with pytest.raises(ValueError, match="m_fixed"):
        RoundPlan.fedavg(net, ServerConfig(t_max=2))


def test_plan_rows_generator_matches_constructor():
    """The row generator and the constructor consume identical rng
    streams -- interleaving foreign draws between rows must not change
    the rows themselves."""
    net, cfg = _net_cfg(t_max=3)
    whole = RoundPlan.connectivity_aware(
        net, cfg, rng=np.random.default_rng(cfg.seed))
    gen = plan_rows(net, cfg, "semidec", np.random.default_rng(cfg.seed))
    rows = [next(gen) for _ in range(cfg.t_max)]
    assert whole.allclose(RoundPlan.from_rows(rows, "semidec"))


def test_plan_with_active_renormalizes_bookkeeping():
    net, cfg = _net_cfg(t_max=3)
    plan = RoundPlan.connectivity_aware(net, cfg)
    active = np.ones_like(plan.active_t)
    active[1, :] = 0.0                       # everyone drops in round 1
    dropped = plan.with_active(active)
    eff = (plan.tau_t * active).sum(axis=1)
    assert (dropped.m_actual_t == eff).all()
    assert (dropped.d2s_t == eff).all()
    np.testing.assert_array_equal(dropped.m_t, np.maximum(eff, 1.0))
    assert dropped.m_t[1] == 1.0             # clamped, like a tau=0 round
    # planner metadata untouched; D2D billing loses the dropped senders'
    # outgoing edges (round 1: everyone silent => zero D2D transmissions)
    np.testing.assert_array_equal(dropped.m_planned_t, plan.m_planned_t)
    np.testing.assert_array_equal(dropped.d2d_t[[0, 2]],
                                  plan.d2d_t[[0, 2]])
    assert dropped.d2d_t[1] == 0 < plan.d2d_t[1]
    # an all-ones mask leaves every column bit-identical
    assert plan.with_active(np.ones_like(plan.active_t)).allclose(plan)

    with pytest.raises(ValueError, match="shape"):
        plan.with_active(np.ones((2, 2)))
    with pytest.raises(ValueError, match="0/1"):
        plan.with_active(np.full_like(plan.active_t, 0.5))
    with pytest.raises(ValueError, match="rate"):
        plan.with_dropout(1.5)


def test_plan_json_round_trip_is_exact():
    net, cfg = _net_cfg(t_max=3)
    for plan in (RoundPlan.connectivity_aware(net, cfg),
                 RoundPlan.fedavg(net, ServerConfig(t_max=2, m_fixed=4)),
                 RoundPlan.connectivity_aware(net, cfg).with_dropout(
                     0.3, np.random.default_rng(2))):
        back = RoundPlan.from_json(plan.to_json())
        assert plan.allclose(back)
        assert back.has_dropout == plan.has_dropout

    with pytest.raises(ValueError, match="version"):
        RoundPlan.from_json('{"version": 99}')


def test_plan_json_round_trip_executes_to_identical_history():
    """to_json -> from_json -> execute == executing the original plan:
    identical History records, metrics, and final params (bitwise)."""
    net, cfg = _net_cfg()
    plan = RoundPlan.connectivity_aware(net, cfg).with_dropout(
        0.3, np.random.default_rng(7))

    def run(p):
        server = FederatedServer(
            net, quad_loss, {"x": jnp.zeros(4)}, _sampler(net.n, 4), cfg,
            execution=ExecutionConfig(backend="fused", scan=True))
        hist = server.run(eval_fn=lambda prm: {
            "l2": float(jnp.sum(prm["x"] ** 2))}, plan=p)
        return server, hist

    s1, h1 = run(plan)
    s2, h2 = run(RoundPlan.from_json(plan.to_json()))
    np.testing.assert_array_equal(np.asarray(s1.params["x"]),
                                  np.asarray(s2.params["x"]))
    for a, b in zip(h1.records, h2.records):
        assert (a.t, a.m, a.m_actual, a.d2s, a.d2d, a.eta, a.metrics) == \
            (b.t, b.m, b.m_actual, b.d2s, b.d2d, b.eta, b.metrics)


def test_server_last_plan_reruns_identically():
    """server.run() exposes the executed plan; re-running it through a
    fresh same-seeded server reproduces the History bitwise (the
    'reproducible trajectories' contract)."""
    s1, h1 = _server(execution=ExecutionConfig(backend="einsum"))
    assert s1.last_plan is not None and not s1.last_plan.has_dropout
    net, cfg = _net_cfg()
    s2 = FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                         _sampler(net.n, 4), cfg,
                         execution=ExecutionConfig(backend="einsum"))
    h2 = s2.run(eval_fn=lambda prm: {"gap": float(jnp.sum(prm["x"] ** 2))})
    np.testing.assert_array_equal(np.asarray(s1.params["x"]),
                                  np.asarray(s2.params["x"]))
    assert s1.last_plan.allclose(s2.last_plan)
    for a, b in zip(h1.records, h2.records):
        assert a.metrics == b.metrics


def test_engine_rejects_mismatched_batches_and_plan():
    net, cfg = _net_cfg(t_max=3)
    plan = RoundPlan.connectivity_aware(net, cfg)
    engine = make_engine(ExecutionConfig(), quad_loss)
    with pytest.raises(ValueError, match="batch"):
        engine.execute(plan, {"x": jnp.zeros(4)}, [None])
    server = FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                             _sampler(net.n, 4),
                             ServerConfig(t_max=3, seed=0))
    small = D2DNetwork(n=6, c=2, k_range=(2, 3))
    other = RoundPlan.connectivity_aware(small,
                                         ServerConfig(t_max=3, seed=0))
    with pytest.raises(ValueError, match="clients"):
        server.run(plan=other)


# ---------------------------------------------------------------------------
# mesh runtime (1-device debug mesh; the 8-device matrix is `-m mesh`)
# ---------------------------------------------------------------------------

def _tiny_mesh_setup():
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import Model

    mesh = make_debug_mesh((1, 1), axes=("data", "model"))
    cfg = get_config("stablelm-1.6b", reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "vocab_size": 64,
                           "name": "tiny-plan"})
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, size=(1, 2, 2, 9)), jnp.int32)
    return mesh, cfg, params, toks


@pytest.mark.parametrize("mixing", ["fused", "fused_rs"])
def test_mesh_train_step_all_ones_active_is_bitwise_noop(mixing):
    from repro.fl import make_train_step

    mesh, cfg, params, toks = _tiny_mesh_setup()
    step = make_train_step(cfg, mesh, mixing=mixing)
    args = (params, toks, jnp.ones((1, 1), jnp.float32),
            jnp.ones((1,), jnp.float32), jnp.float32(1.0),
            jnp.float32(0.05))
    out0 = step(*args)
    out1 = step(*args, active=jnp.ones((1,), jnp.float32))
    for a, b in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mixing", ["einsum", "fused", "fused_rs"])
def test_mesh_train_step_dropped_client_is_identity(mixing):
    """All clients dropped => zero aggregate => globals unchanged, on
    every mesh mixing schedule (the mesh analogue of the tau=0 round)."""
    from repro.fl import make_train_step

    mesh, cfg, params, toks = _tiny_mesh_setup()
    step = make_train_step(cfg, mesh, mixing=mixing)
    out = step(params, toks, jnp.ones((1, 1), jnp.float32),
               jnp.ones((1,), jnp.float32), jnp.float32(1.0),
               jnp.float32(0.05), active=jnp.zeros((1,), jnp.float32))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_scanned_with_active_bitwise_vs_sequential():
    from repro.fl import make_scanned_train_steps, make_train_step

    mesh, cfg, params, _ = _tiny_mesh_setup()
    K = 2
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 64, size=(K, 1, 2, 2, 9)),
                       jnp.int32)
    A_seq = jnp.ones((K, 1, 1), jnp.float32)
    tau_seq = jnp.ones((K, 1), jnp.float32)
    m_seq = jnp.ones((K,), jnp.float32)
    eta_seq = jnp.asarray([0.05, 0.02], jnp.float32)
    act_seq = jnp.asarray([[1.0], [0.0]], jnp.float32)

    step = make_train_step(cfg, mesh, mixing="fused")
    seq = params
    for t in range(K):
        seq = step(seq, toks[t], A_seq[t], tau_seq[t], m_seq[t],
                   eta_seq[t], active=act_seq[t])
    scanned = make_scanned_train_steps(cfg, mesh, K, mixing="fused")
    final, _ = scanned(params, toks, A_seq, tau_seq, m_seq, eta_seq,
                       active_seq=act_seq)
    for a, b in zip(jax.tree.leaves(seq), jax.tree.leaves(final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
