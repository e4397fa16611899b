"""StreamEngine: bitwise synchronous equivalence, staleness-weight
degeneracy, fault replay, graceful degradation, and the backend matrix.

The lock-down contract (ISSUE 6 acceptance criteria):

* zero staleness + full buffer + no faults reproduces the synchronous
  ``LocalEngine`` History bitwise, per backend;
* any seeded ``FaultSpec`` trajectory replays bitwise from its JSON
  round-trip;
* a zero-latency fault trace streamed semi-asynchronously equals the
  synchronous engine on ``plan.with_faults(trace)`` bitwise.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import D2DNetwork, FederatedServer, ServerConfig
from repro.fl import (ExecutionConfig, FaultSpec, LocalEngine, RoundPlan,
                      StreamConfig, StreamEngine, make_engine,
                      resolve_backend, sample_trace, staleness_weight)
from repro.kernels.mixing.ops import combine_weights

jax.config.update("jax_enable_x64", False)

STREAM_BACKENDS = ("einsum", "fused", "aggregate")


def quad_loss(params, batch):
    x = params["x"]
    b, = batch
    return 0.5 * jnp.sum((x - b.mean(axis=0)) ** 2)


def _setup(n=12, c=2, K=6, p=4, T=3, seed=3, batch_seed=7):
    net = D2DNetwork(n=n, c=c, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=T, t_max=K, phi_max=0.3, seed=seed,
                       eta=lambda t: 0.2)
    plan = RoundPlan.connectivity_aware(net, cfg)
    rng = np.random.default_rng(batch_seed)
    targets = rng.standard_normal((n, p)).astype(np.float32)
    batches = [
        (jnp.asarray(targets[:, None, None, :]
                     + 0.05 * rng.standard_normal((n, T, 2, p)),
                     jnp.float32),)
        for _ in range(K)]
    return plan, {"x": jnp.zeros(p)}, batches


def _eval(prm):
    return {"l2": float(jnp.sum(prm["x"] ** 2))}


def _records_equal(h1, h2, check_stream=True):
    assert len(h1.records) == len(h2.records)
    for r1, r2 in zip(h1.records, h2.records):
        assert (r1.t, r1.m, r1.m_actual, r1.d2s, r1.d2d) == \
            (r2.t, r2.m, r2.m_actual, r2.d2s, r2.d2d)
        assert r1.eta == r2.eta
        assert r1.psi_bound == r2.psi_bound or (
            math.isnan(r1.psi_bound) and math.isnan(r2.psi_bound))
        assert r1.metrics == r2.metrics
        if check_stream:
            assert r1.stream == r2.stream
    assert h1.ledger.total_d2s == h2.ledger.total_d2s
    assert h1.ledger.total_d2d == h2.ledger.total_d2d


# ---------------------------------------------------------------------------
# Bitwise equivalence with the synchronous engine (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", STREAM_BACKENDS)
def test_no_fault_stream_reproduces_local_engine_bitwise(backend):
    plan, params0, batches = _setup()
    p1, h1 = LocalEngine(quad_loss, ExecutionConfig(backend=backend)) \
        .execute(plan, params0, batches, eval_fn=_eval)
    p2, h2 = make_engine(
        ExecutionConfig(backend=backend, stream=StreamConfig()),
        quad_loss).execute(plan, params0, batches, eval_fn=_eval)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    assert all(r.stream is None for r in h2.records)
    _records_equal(h1, h2, check_stream=False)


@pytest.mark.parametrize("backend", STREAM_BACKENDS)
def test_full_buffer_zero_latency_equals_sync(backend):
    """Satellite: b = n with zero latency is the synchronous round."""
    plan, params0, batches = _setup()
    p1, _ = LocalEngine(quad_loss, ExecutionConfig(backend=backend)) \
        .execute(plan, params0, batches)
    p2, h2 = make_engine(
        ExecutionConfig(backend=backend,
                        stream=StreamConfig(buffer=plan.n_clients)),
        quad_loss).execute(plan, params0, batches)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    assert all(r.stream is None for r in h2.records)


def test_dropout_plan_no_latency_stream_matches_local():
    """Straggler masks flow through the stream fast path bitwise."""
    plan, params0, batches = _setup()
    plan = plan.with_dropout(0.3, np.random.default_rng(5))
    p1, h1 = LocalEngine(quad_loss, ExecutionConfig()) \
        .execute(plan, params0, batches, eval_fn=_eval)
    p2, h2 = make_engine(ExecutionConfig(stream=StreamConfig()),
                         quad_loss) \
        .execute(plan, params0, batches, eval_fn=_eval)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    _records_equal(h1, h2, check_stream=False)


def test_zero_latency_faults_equal_with_faults_local_run():
    """Failure chains with no latency reduce to plan straggler masks:
    the stream run and the synchronous run on plan.with_faults(trace)
    are bitwise-identical."""
    plan, params0, batches = _setup()
    spec = FaultSpec(failures="markov",
                     failure_params={"p_fail": 0.3, "p_recover": 0.5})
    stream_eng = make_engine(
        ExecutionConfig(stream=StreamConfig(faults=spec, fault_seed=11)),
        quad_loss)
    p2, h2 = stream_eng.execute(plan, params0, batches, eval_fn=_eval)
    trace = sample_trace(spec, n=plan.n_clients, K=plan.n_rounds, seed=11)
    p1, h1 = LocalEngine(quad_loss, ExecutionConfig()) \
        .execute(plan.with_faults(trace), params0, batches, eval_fn=_eval)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    _records_equal(h1, h2, check_stream=False)
    assert stream_eng.last_realized_plan.allclose(plan.with_faults(trace))


# ---------------------------------------------------------------------------
# Staleness-weight degeneracy (satellite property tests)
# ---------------------------------------------------------------------------

def test_staleness_weight_values():
    assert staleness_weight(0, "poly", 0.7) == 1.0
    assert staleness_weight(0, "exp", 0.3) == 1.0
    assert staleness_weight(3, "none") == 1.0
    assert staleness_weight(1, "poly", 1.0) == pytest.approx(0.5)
    assert staleness_weight(2, "exp", 0.5) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        staleness_weight(1, "nope")


def test_combine_weights_unit_weight_is_bitwise_noop():
    """weights=1.0 (and an all-ones vector) reduce exactly to the
    active_t mask path -- same floats, bit for bit."""
    rng = np.random.default_rng(0)
    n = 10
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    tau = jnp.asarray((rng.random(n) < 0.6), jnp.float32)
    act = jnp.asarray((rng.random(n) < 0.8), jnp.float32)
    m = jnp.float32(4.0)
    base = combine_weights(A, tau, m, act)
    for w in (jnp.float32(1.0), jnp.ones(n, jnp.float32)):
        np.testing.assert_array_equal(
            np.asarray(combine_weights(A, tau, m, act, w)),
            np.asarray(base))
    # and a real discount changes only the upload leg scale
    half = combine_weights(A, tau, m, act, jnp.float32(0.5))
    np.testing.assert_allclose(np.asarray(half), 0.5 * np.asarray(base),
                               rtol=1e-6)


def test_stale_path_weight_one_matches_fast_path():
    """Force the buffered (stale) aggregation path with weight 1.0 and
    compare against the synchronous result: same numbers to float
    tolerance (different jit partitioning, same algebra)."""
    plan, params0, batches = _setup(K=4)
    # deadline 0.5 with fixed latency 1.0: every cohort misses its own
    # closure and is consumed one round late at weight 1.0 ('none')
    spec = FaultSpec(latency="fixed", latency_params={"value": 1.0})
    p2, h2 = make_engine(
        ExecutionConfig(backend="aggregate",
                        stream=StreamConfig(deadline=0.5,
                                            faults=spec,
                                            staleness="none")),
        quad_loss).execute(plan, params0, batches)
    assert any(r.stream and r.stream.get("late") for r in h2.records)
    # every record's weighted divisor stays the raw count at weight 1.0
    assert all(not r.stream or "m_weighted" not in r.stream
               for r in h2.records)


# ---------------------------------------------------------------------------
# Replay (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("einsum", "aggregate"))
def test_fault_trajectory_replays_bitwise_from_json(backend):
    plan, params0, batches = _setup()
    spec = FaultSpec(failures="iid", failure_params={"rate": 0.2},
                     latency="exponential", latency_params={"mean": 0.8},
                     duplicate_rate=0.2, depart_rate=0.02)

    def run(s):
        eng = make_engine(
            ExecutionConfig(backend=backend,
                            stream=StreamConfig(buffer=6, deadline=1.0,
                                                staleness="poly",
                                                staleness_param=0.5,
                                                faults=s, fault_seed=5)),
            quad_loss)
        prm, hist = eng.execute(plan, params0, batches, eval_fn=_eval)
        return prm, hist, eng

    p1, h1, e1 = run(spec)
    p2, h2, e2 = run(FaultSpec.from_json(spec.to_json()))
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    _records_equal(h1, h2)
    assert e1.last_trace.allclose(e2.last_trace)
    assert e1.last_realized_plan.allclose(e2.last_realized_plan)
    assert e1.last_closures == e2.last_closures


def test_realized_plan_is_a_replayable_artifact():
    """Executing the saved realized plan (faults folded into columns)
    with NO fault spec reproduces the faulty run bitwise -- the
    --plan-out artifact of a stream run pins the whole trajectory."""
    plan, params0, batches = _setup()
    spec = FaultSpec(failures="iid", failure_params={"rate": 0.25},
                     latency="uniform",
                     latency_params={"lo": 0.0, "hi": 1.4})
    stream = StreamConfig(buffer=5, deadline=1.0, staleness="poly")
    eng = make_engine(
        ExecutionConfig(stream=dataclasses.replace(stream, faults=spec)),
        quad_loss)
    p1, h1 = eng.execute(plan, params0, batches)
    realized = RoundPlan.from_json(eng.last_realized_plan.to_json())
    p2, h2 = make_engine(ExecutionConfig(stream=stream), quad_loss) \
        .execute(realized, params0, batches)
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    # duplicate deliveries live in the trace, not the plan columns, so
    # compare everything except the dup-inflated d2s totals
    for r1, r2 in zip(h1.records, h2.records):
        assert (r1.t, r1.m, r1.m_actual, r1.d2d) == \
            (r2.t, r2.m, r2.m_actual, r2.d2d)


# ---------------------------------------------------------------------------
# Degradation semantics
# ---------------------------------------------------------------------------

def test_deadline_shortfall_recorded_not_fatal():
    plan, params0, batches = _setup()
    spec = FaultSpec(latency="fixed", latency_params={"value": 5.0})
    p2, h2 = make_engine(
        ExecutionConfig(stream=StreamConfig(deadline=1.0, max_staleness=0,
                                            faults=spec)),
        quad_loss).execute(plan, params0, batches)
    # nothing ever arrives in time and everything over-stales away:
    # all rounds degrade gracefully to identity updates
    assert all(r.m_actual == 0 for r in h2.records)
    assert all(r.stream["deadline_hit"] == 1.0 for r in h2.records)
    assert sum(r.stream.get("lost", 0) for r in h2.records) > 0
    np.testing.assert_array_equal(np.asarray(p2["x"]),
                                  np.asarray(params0["x"]))


def test_departures_shrink_participation_permanently():
    plan, params0, batches = _setup(K=8)
    spec = FaultSpec(depart_rate=0.2)
    eng = make_engine(
        ExecutionConfig(stream=StreamConfig(faults=spec, fault_seed=3)),
        quad_loss)
    _, hist = eng.execute(plan, params0, batches)
    gone = int((eng.last_trace.depart_round < 8).sum())
    assert gone > 0
    # the last round's survivors exclude every departed client
    last_active = eng.last_realized_plan.active_t[-1]
    assert (last_active[eng.last_trace.depart_round < 8] == 0).all()


def test_duplicates_billed_as_uplink_but_aggregated_once():
    plan, params0, batches = _setup()
    base = StreamConfig()
    dup = StreamConfig(faults=FaultSpec(duplicate_rate=0.9), fault_seed=2)
    p1, h1 = make_engine(ExecutionConfig(stream=base), quad_loss) \
        .execute(plan, params0, batches)
    p2, h2 = make_engine(ExecutionConfig(stream=dup), quad_loss) \
        .execute(plan, params0, batches)
    # params identical: duplicates are deduplicated before aggregation
    np.testing.assert_array_equal(np.asarray(p1["x"]), np.asarray(p2["x"]))
    # but the uplink ledger bills them
    assert h2.ledger.total_d2s > h1.ledger.total_d2s
    assert sum(r.stream.get("dup", 0) for r in h2.records if r.stream) \
        == h2.ledger.total_d2s - h1.ledger.total_d2s


def test_buffered_closure_accepts_stragglers_late():
    plan, params0, batches = _setup()
    spec = FaultSpec(latency="exponential", latency_params={"mean": 1.2})
    _, hist = make_engine(
        ExecutionConfig(stream=StreamConfig(buffer=4, deadline=2.0,
                                            staleness="poly",
                                            faults=spec, fault_seed=9)),
        quad_loss).execute(plan, params0, batches)
    late = sum(r.stream.get("late", 0) for r in hist.records if r.stream)
    assert late > 0
    weighted = [r.stream["m_weighted"] for r in hist.records
                if r.stream and "m_weighted" in r.stream]
    # staleness discounts pull the weighted divisor under the raw count
    assert weighted and all(
        w < r.m_actual for w, r in zip(
            weighted, (r for r in hist.records
                       if r.stream and "m_weighted" in r.stream)))


# ---------------------------------------------------------------------------
# Engine / config matrix
# ---------------------------------------------------------------------------

def test_resolve_backend_stream_matrix():
    assert resolve_backend(
        ExecutionConfig(backend="aggregate", stream=StreamConfig())) \
        == "aggregate"
    assert resolve_backend(
        ExecutionConfig(backend="fused", stream=StreamConfig())) \
        == "aggregate"
    assert resolve_backend(
        ExecutionConfig(backend="einsum", stream=StreamConfig())) \
        == "einsum"
    with pytest.raises(ValueError, match="scan"):
        resolve_backend(ExecutionConfig(scan=True, stream=StreamConfig()))
    with pytest.raises(ValueError, match="record_mixed"):
        resolve_backend(ExecutionConfig(backend="fused",
                                        record_mixed=True,
                                        stream=StreamConfig()))
    with pytest.raises(ValueError, match="mesh"):
        resolve_backend(ExecutionConfig(stream=StreamConfig(),
                                        mesh=object(), model_cfg=object()))
    with pytest.raises(ValueError):
        resolve_backend(ExecutionConfig(backend="ring",
                                        stream=StreamConfig()))


def test_stream_config_validation():
    with pytest.raises(ValueError):
        StreamConfig(buffer=0)
    with pytest.raises(ValueError):
        StreamConfig(deadline=0.0)
    with pytest.raises(ValueError):
        StreamConfig(staleness="nope")
    with pytest.raises(ValueError):
        StreamConfig(max_staleness=-1)


def test_engine_construction_guards():
    with pytest.raises(ValueError, match="stream"):
        StreamEngine(quad_loss, ExecutionConfig())
    with pytest.raises(ValueError, match="synchronous"):
        LocalEngine(quad_loss, ExecutionConfig(stream=StreamConfig()))
    assert isinstance(
        make_engine(ExecutionConfig(stream=StreamConfig()), quad_loss),
        StreamEngine)


# ---------------------------------------------------------------------------
# Server integration (incl. the split-rng satellite)
# ---------------------------------------------------------------------------

def _server(stream=None, seed=2, t_max=5, execution=None):
    rng = np.random.default_rng(0)
    n, p, T = 12, 3, 3
    targets = rng.standard_normal((n, p)).astype(np.float32)

    def sampler(r, t):
        samp = targets[:, None, None, :] \
            + 0.05 * r.standard_normal((n, T, 2, p))
        return (jnp.asarray(samp, jnp.float32),)

    net = D2DNetwork(n=n, c=2, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=T, t_max=t_max, phi_max=0.3, seed=seed,
                       eta=lambda t: 0.2)
    if execution is None:
        execution = ExecutionConfig(stream=stream)
    return FederatedServer(net, quad_loss, {"x": jnp.zeros(p)}, sampler,
                           cfg, algorithm="semidec", execution=execution)


def test_server_runs_stream_engine():
    spec = FaultSpec(failures="iid", failure_params={"rate": 0.2},
                     latency="exponential")
    srv = _server(StreamConfig(buffer=6, deadline=1.5, staleness="poly",
                               faults=spec))
    hist = srv.run(eval_fn=_eval)
    assert len(hist.records) == 5
    assert srv.effective_backend == "einsum"
    assert srv.last_plan is not None


def test_server_built_plans_regenerate():
    """Split rng streams: the server's own plans now embed their seed
    and regenerate() end-to-end (the carried ROADMAP item)."""
    srv = _server(None, execution=ExecutionConfig())
    srv.run()
    plan = srv.last_plan
    assert plan.seed == srv.config.seed
    assert plan.topology is not None
    regen = RoundPlan.from_json(plan.to_json()).regenerate()
    assert regen.allclose(plan)


def test_replay_consumes_identical_batch_stream():
    """Because batches no longer interleave with planning draws,
    replaying the saved plan reproduces the original run bitwise."""
    srv1 = _server(None, execution=ExecutionConfig())
    h1 = srv1.run(eval_fn=_eval)
    srv2 = _server(None, execution=ExecutionConfig())
    h2 = srv2.run(eval_fn=_eval, plan=srv1.last_plan)
    np.testing.assert_array_equal(np.asarray(srv1.params["x"]),
                                  np.asarray(srv2.params["x"]))
    _records_equal(h1, h2)
