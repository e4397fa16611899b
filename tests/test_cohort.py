"""The client-sequential cohort form: a round that trains the clients one
at a time under ``lax.scan`` and accumulates the eq.-4 aggregate equals
the vmapped round (allclose: the sum runs in another order); the engine
picks it only where the vmapped round's param copies do not fit the
device, counts its form, keys its program by it, and refuses what the
form cannot serve.  The ``cnn70-paper`` cut keeps today's vmapped round."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import D2DNetwork, ServerConfig, make_round_fn
from repro.fl import ExecutionConfig, RoundPlan, make_engine
from repro.fl import engine as engine_module
from repro.fl.engine import cohort_form
from repro.fl.packing import QuantSpec
from repro.models.config import ModelConfig
from repro.models.model import Model

N, T, B, S = 4, 2, 2, 16


def _tiny_lm():
    """A two-layer MLA + MoE LM on the dense expert oracle, holding 4 of
    its 8 experts."""
    cfg = ModelConfig(
        name="tiny-moe", family="moe", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=48, vocab_size=64, mla=True, kv_lora_rank=16,
        rope_head_dim=8, nope_head_dim=8, v_head_dim=8, n_experts=8,
        experts_per_token=3, n_shared_experts=1, moe_d_ff=16,
        first_dense_layers=1, norm_topk_prob=False, experts_held=4,
        expert_offset=2, yarn_factor=40.0, yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707, moe_impl="dense", remat=False)
    return Model(cfg)


def _plan(seed, K=2, dropout=False):
    net = D2DNetwork(n=N, c=2, k_range=(2, 2), p_fail=0.1)
    plan = RoundPlan.connectivity_aware(net, ServerConfig(
        T=T, t_max=K, phi_max=0.5, seed=seed, eta=lambda t: 0.05))
    return plan.with_dropout(0.3, np.random.default_rng(seed)) \
        if dropout else plan


def _batches(seed, K=2):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(K):
        toks = rng.integers(0, 64, (N, T, B, S + 1))
        out.append((jnp.asarray(toks[..., :-1], jnp.int32),
                    jnp.asarray(toks[..., 1:], jnp.int32)))
    return out


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


@pytest.mark.parametrize("dropout", [False, True], ids=["full", "dropout"])
def test_sequential_round_equals_the_vmapped_round(dropout):
    model = _tiny_lm()
    params = model.init(jax.random.key(0))
    plan, batches = _plan(3, dropout=dropout), _batches(3)
    vmapped = make_round_fn(model.loss, mixing_backend="aggregate")
    seq = make_round_fn(model.loss, mixing_backend="aggregate",
                        cohort="sequential")
    a, b = _copy(params), _copy(params)
    for t in range(plan.n_rounds):
        row = plan[t]
        args = (batches[t], jnp.asarray(row.A, jnp.float32),
                jnp.asarray(row.tau, jnp.float32),
                jnp.asarray(row.m, jnp.float32),
                jnp.asarray(row.eta, jnp.float32),
                jnp.asarray(row.active, jnp.float32))
        a, mixed = vmapped(a, *args)
        b, none = seq(b, *args)
        assert mixed is None and none is None
    moved = 0
    for x, y, p in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=2e-6,
                                   atol=2e-7)
        moved += float(jnp.max(jnp.abs(x - p))) > 1e-4
    assert moved > len(jax.tree.leaves(params)) // 2


def test_cohort_form_follows_the_param_copies():
    params = {"w": jax.ShapeDtypeStruct((1000,), jnp.float32)}   # 4000 B
    assert cohort_form(params, 4, None) == "vmap"
    assert cohort_form(params, 4, 9 * 4000) == "vmap"
    assert cohort_form(params, 4, 9 * 4000 - 1) == "sequential"


def _quad(params, batch):
    return 0.5 * jnp.sum((params["x"] - batch[0].mean(axis=0)) ** 2)


def _quad_data(seed, K=2, P=5):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.standard_normal((N, T, 3, P)), jnp.float32),)
            for _ in range(K)]


@pytest.mark.parametrize("scan", [False, True], ids=["loop", "scan"])
@pytest.mark.parametrize("backend", ["aggregate", "sparse_aggregate"])
def test_engine_takes_the_sequential_form_where_copies_do_not_fit(
        monkeypatch, backend, scan):
    params = {"x": jnp.linspace(-1.0, 1.0, 5, dtype=jnp.float32)}
    cfg = ExecutionConfig(backend=backend, scan=scan)
    want, _ = make_engine(cfg, _quad).execute(_plan(1), params,
                                              _quad_data(1))
    monkeypatch.setattr(engine_module, "_bytes_limit", lambda: 64)
    engine = make_engine(cfg, _quad)
    rec = spans.Recorder()
    with spans.recording(rec):
        got, hist = engine.execute(_plan(1), _copy(params), _quad_data(1))
        np.testing.assert_allclose(np.asarray(got["x"]),
                                   np.asarray(want["x"]), rtol=1e-6,
                                   atol=1e-7)
        engine.execute(_plan(2), got, _quad_data(2))
    # the per-round program consumes the params it is given
    assert got["x"].is_deleted() != scan
    assert rec.counters[("engine.cohort", "sequential")] == [0.0, 2]
    assert ("engine.cohort", "vmap") not in rec.counters
    assert set(engine._programs) == (
        {("scan", 2, "sequential", None)} if scan
        else {("round", "sequential", None)})
    assert len(hist.records) == 2


@pytest.mark.parametrize("backend, quant", [
    ("einsum", None), ("fused", None), ("aggregate", "int8")])
def test_sequential_form_refuses_what_it_cannot_serve(monkeypatch, backend,
                                                      quant):
    monkeypatch.setattr(engine_module, "_bytes_limit", lambda: 64)
    q = QuantSpec(storage=quant, block=512) if quant else None
    cfg = ExecutionConfig(backend=backend, record_mixed=backend == "fused",
                          quant=q)
    params = {"x": jnp.zeros(5, jnp.float32)}
    with pytest.raises(ValueError, match="sequential cohort"):
        make_engine(cfg, _quad).execute(_plan(1), params, _quad_data(1))


def test_cnn_cut_keeps_the_vmapped_round():
    """``cnn70-paper``'s tiny cut on this host: the engine counts the
    vmapped form and its round program compiles to the text of today's
    ``make_round_fn`` round, as ``local_sgd_ms`` compiles it."""
    from bench.tests.tiny import tiny_cell
    from bench.harness.spans import Spans
    from bench.metrics.local_sgd_ms import round_program_text

    cell = tiny_cell()
    system = cell.system().System(cell, 5, Spans(), jax.devices())
    rec = spans.Recorder()
    with spans.recording(rec):
        system.setup()
    assert rec.counters[("engine.cohort", "vmap")][1] >= 1
    assert ("engine.cohort", "sequential") not in rec.counters
    engine = system.server.engine
    assert set(engine._programs) == {("round", "vmap", None)}
    program = engine._programs[("round", "vmap", None)]
    today = make_round_fn(engine.loss_fn, mixing_backend=engine.backend,
                          chunk=engine.cfg.chunk,
                          interpret=engine.cfg.interpret)
    ours = _instructions(_text(program, system))
    assert ours == _instructions(_text(today, system)) == \
        _instructions(round_program_text(system))


def _instructions(text):
    """Each instruction's name, result shape and op_name: the compiled
    program without the call-site records of the process compiling it."""
    import re

    from bench.metrics.local_sgd_ms import _INSTRUCTION, _OP_NAME, \
        result_shape

    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), result_shape(m.group(2)),
                        op.group(1) if op else None,
                        re.sub(r"\w+=\{[^{}]*\}|metadata=\{.*?\}", "",
                               m.group(2))))
    return out


def _text(round_fn, system):
    from functools import partial

    from bench.systems.fl_cnn import init_params

    model, pop, tr = (system.cfg["model"], system.cfg["population"],
                      system.cfg["training"])
    n, T_, B_, hw = pop["n"], tr["T"], tr["batch"], model["image_hw"]
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(partial(init_params, model=model),
                            jax.random.key(0))
    batches = (shape((n, T_, B_, hw, hw, model["channels"]), f32),
               shape((n, T_, B_), jnp.int32))
    return round_fn.lower(params, batches, shape((n, n), f32),
                          shape((n,), f32), shape((), f32),
                          shape((), f32)).compile().as_text()
