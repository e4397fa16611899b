"""Tests: data pipeline, optimizers, checkpointing, paper CNN."""

import collections
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro import spans
from repro.ckpt import latest_checkpoint, load_checkpoint, save_checkpoint
from repro.data import (Dataset, FederatedBatcher, dirichlet_partition,
                        iid_partition, label_sorted_partition,
                        make_classification, make_token_stream, lm_batches)
from repro.core.rounds import make_round_fn
from repro.models.cnn import (accuracy, cnn_apply, init_cnn, init_logreg,
                              init_mlp, l2_regularized_loss, logreg_apply,
                              mlp_apply, softmax_xent)
from repro.optim import adam, clip_by_global_norm, momentum, sgd
from repro.optim.schedules import (cosine, inverse_time, paper_experimental,
                                   warmup_cosine)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def test_classification_dataset_shapes_and_determinism():
    ds1 = make_classification(n_samples=500, seed=3)
    ds2 = make_classification(n_samples=500, seed=3)
    assert ds1.x.shape == (500, 28, 28, 1) and ds1.y.shape == (500,)
    np.testing.assert_array_equal(ds1.x, ds2.x)
    assert set(np.unique(ds1.y)) <= set(range(10))


@given(st.integers(2, 20), st.integers(1, 3))
@settings(max_examples=20, deadline=None)
def test_label_sorted_partition_properties(n_clients, shards):
    ds = make_classification(n_samples=1200, seed=0)
    parts = label_sorted_partition(ds, n_clients, shards_per_client=shards)
    assert len(parts) == n_clients
    all_idx = np.concatenate(parts)
    assert len(all_idx) == len(np.unique(all_idx))   # disjoint
    # shards are contiguous intervals of the label-sorted order, so the
    # total number of (shard, label) incidences is at most
    # n_shards + n_labels - 1; per client that sums over its shards.
    n_shards = n_clients * shards
    total_incidences = sum(len(np.unique(ds.y[p])) for p in parts)
    assert total_incidences <= n_shards + 10 - 1


def test_label_sorted_partition_extreme_heterogeneity():
    """Paper: 70 clients, 2 chunks each => ~2 labels per client."""
    ds = make_classification(n_samples=7000, seed=1)
    parts = label_sorted_partition(ds, 70, 2)
    label_counts = [len(np.unique(ds.y[p])) for p in parts]
    assert np.mean(label_counts) <= 3.0


def test_dirichlet_and_iid_partitions_cover():
    ds = make_classification(n_samples=1000, seed=2)
    for parts in (dirichlet_partition(ds, 10, 0.5), iid_partition(ds, 10)):
        total = sum(len(p) for p in parts)
        assert total >= 0.9 * len(ds)
        all_idx = np.concatenate(parts)
        assert len(all_idx) == len(np.unique(all_idx))


def test_federated_batcher_shapes():
    ds = make_classification(n_samples=600, seed=0)
    parts = label_sorted_partition(ds, 6, 2)
    batcher = FederatedBatcher(ds, parts, T=4, batch_size=8)
    x, y = batcher(np.random.default_rng(0), 0)
    assert x.shape == (6, 4, 8, 28, 28, 1)
    assert y.shape == (6, 4, 8)


def _host_gather(ds, parts, T, B, rng):
    """The batch draw as a host gather: the oracle for the device one."""
    xs = np.empty((len(parts), T, B) + ds.x.shape[1:], dtype=ds.x.dtype)
    ys = np.empty((len(parts), T, B), dtype=ds.y.dtype)
    for i, part in enumerate(parts):
        idx = rng.choice(part, size=(T, B), replace=True)
        xs[i] = ds.x[idx]
        ys[i] = ds.y[idx]
    return jnp.asarray(xs), jnp.asarray(ys)


def _batcher_data(kind):
    rng = np.random.default_rng(11)
    if kind == "flat":
        ds = Dataset(rng.standard_normal((500, 20)).astype(np.float32),
                     rng.integers(0, 5, 500))
        return ds, iid_partition(ds, 5)
    if kind == "lane_aligned":              # 128 features: rows unpadded
        ds = Dataset(rng.standard_normal((300, 16, 8)).astype(np.float32),
                     rng.integers(0, 3, 300))
    else:
        ds = make_classification(n_samples=600, seed=0)
    if kind == "image":
        return ds, label_sorted_partition(ds, 6, 2)
    n = len(ds)
    return ds, [np.arange(0, 3), np.arange(3, 40), np.arange(40, n, 7),
                np.array([n - 1])]


@pytest.mark.parametrize("kind", ["image", "flat", "unequal",
                                  "lane_aligned"])
def test_federated_batcher_matches_host_gather_bitwise(kind):
    ds, parts = _batcher_data(kind)
    T, B = 3, 5
    batcher = FederatedBatcher(ds, parts, T=T, batch_size=B)
    rng, rng_ref = np.random.default_rng(5), np.random.default_rng(5)
    for t in range(3):
        x, y = batcher(rng, t)
        x_ref, y_ref = _host_gather(ds, parts, T, B, rng_ref)
        for got, want in ((x, x_ref), (y, y_ref)):
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert rng.bit_generator.state == rng_ref.bit_generator.state


def test_federated_batcher_sends_only_indices_after_first_call(capfd):
    ds = make_classification(n_samples=600, seed=0)
    batcher = FederatedBatcher(ds, label_sorted_partition(ds, 6, 2), T=4,
                               batch_size=8)
    rng = np.random.default_rng(0)
    jax.block_until_ready(batcher(rng, 0))
    capfd.readouterr()
    # logs every host-to-device transfer, explicit or implicit
    with jax.transfer_guard_host_to_device("log_explicit"):
        jax.block_until_ready(batcher(rng, 1))
    sent = re.findall(r"host-to-device transfer: aval=ShapedArray\((.*?)\)",
                      capfd.readouterr().err)
    assert sent == ["int32[6,4,8]"]


def test_federated_batcher_rejects_indices_outside_the_dataset():
    ds = make_classification(n_samples=50, seed=0)
    with pytest.raises(ValueError, match="outside"):
        FederatedBatcher(ds, [np.arange(10), np.array([3, 50])], T=1,
                         batch_size=2)


def test_token_stream_and_lm_batches():
    toks = make_token_stream(n_tokens=4096, vocab=97, seed=0)
    assert toks.min() >= 0 and toks.max() < 97
    x, y = lm_batches(toks, np.random.default_rng(0), n_clients=4, T=2,
                      batch_size=3, seq_len=16)
    assert x.shape == (4, 2, 3, 16) and y.shape == x.shape
    # causal shift property
    x0 = np.asarray(x[0, 0, 0])
    y0 = np.asarray(y[0, 0, 0])
    np.testing.assert_array_equal(x0[1:], y0[:-1])


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _rosenbrock_grad_steps(opt, steps=400, lr=2e-3, jit_step=True):
    params = {"x": jnp.array([-1.0, 1.5])}

    def loss(p):
        x, y = p["x"][0], p["x"][1]
        return (1 - x) ** 2 + 100 * (y - x ** 2) ** 2

    state = opt.init(params)

    @jax.jit
    def one(params, state):
        g = jax.grad(loss)(params)
        return opt.update(g, state, params, jnp.float32(lr))

    for _ in range(steps):
        params, state = one(params, state)
    return float(loss(params))


def test_sgd_momentum_adam_descend():
    assert _rosenbrock_grad_steps(sgd()) < 4.0
    assert _rosenbrock_grad_steps(momentum(0.9)) < 1.0
    assert _rosenbrock_grad_steps(adam(), steps=2000, lr=2e-2) < 0.1


def test_adam_bias_correction_first_step():
    opt = adam(b1=0.9, b2=0.999)
    params = {"w": jnp.array([1.0])}
    state = opt.init(params)
    g = {"w": jnp.array([0.5])}
    new, _ = opt.update(g, state, params, jnp.float32(0.1))
    # first Adam step is ~ -lr * sign-ish: m_hat/sqrt(v_hat) = 1
    np.testing.assert_allclose(np.asarray(new["w"]), [0.9], atol=1e-5)


def test_clip_by_global_norm():
    g = {"a": jnp.array([3.0, 4.0])}           # norm 5
    clipped = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), [0.6, 0.8],
                               rtol=1e-5)
    unclipped = clip_by_global_norm(g, 10.0)
    np.testing.assert_allclose(np.asarray(unclipped["a"]), [3.0, 4.0],
                               rtol=1e-5)


def test_schedules():
    assert paper_experimental()(0) == pytest.approx(0.02)
    assert paper_experimental()(1) == pytest.approx(0.002)
    s = inverse_time(4.0, 10.0)
    assert s(0) == pytest.approx(0.4) and s(10) == pytest.approx(0.2)
    c = cosine(1.0, 100)
    assert c(0) == pytest.approx(1.0) and c(100) == pytest.approx(0.0, abs=1e-9)
    w = warmup_cosine(1.0, 10, 110)
    assert w(0) == pytest.approx(0.1) and w(9) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    params = {"layer": {"w": jnp.arange(6.0).reshape(2, 3),
                        "b": jnp.zeros(3)},
              "head": jnp.ones((4,), jnp.float32)}
    p = save_checkpoint(str(tmp_path), 7, params, meta={"m_next": 12})
    assert latest_checkpoint(str(tmp_path)) == p
    restored, meta = load_checkpoint(p, jax.tree.map(jnp.zeros_like, params))
    assert meta["step"] == 7 and meta["meta"]["m_next"] == 12
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_mismatch(tmp_path):
    params = {"w": jnp.ones(3)}
    for s in range(5):
        save_checkpoint(str(tmp_path), s, params, keep=2)
    ckpts = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(ckpts) == 2
    bad = {"w": jnp.ones(3), "extra": jnp.ones(1)}
    with pytest.raises(ValueError):
        load_checkpoint(latest_checkpoint(str(tmp_path)), bad)
    with pytest.raises(ValueError):
        load_checkpoint(latest_checkpoint(str(tmp_path)),
                        {"w": jnp.ones((4,))})


# ---------------------------------------------------------------------------
# Paper CNN / MLP / logreg
# ---------------------------------------------------------------------------

def test_cnn_shapes_and_param_count():
    params = init_cnn(seed=0)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # paper reports ~1.66M for this architecture
    assert abs(n_params - 1_663_370) < 10_000
    x = jnp.zeros((2, 28, 28, 1))
    logits = cnn_apply(params, x)
    assert logits.shape == (2, 10)
    assert not bool(jnp.isnan(logits).any())


def test_models_learn_synthetic_task():
    ds = make_classification(n_samples=1024, seed=0)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)

    for init, apply, lr in ((init_mlp, mlp_apply, 0.1),
                            (init_logreg, logreg_apply, 0.1)):
        params = init(seed=0)

        @jax.jit
        def step(p, xb, yb):
            g = jax.grad(lambda q: softmax_xent(apply(q, xb), yb))(p)
            return jax.tree.map(lambda a, b: a - lr * b, p, g)

        for i in range(60):
            sl = slice((i * 64) % 1024, (i * 64) % 1024 + 64)
            params = step(params, x[sl], y[sl])
        acc = accuracy(apply, params, x, y)
        assert acc > 0.6, f"{apply.__name__} failed to learn: acc={acc}"


def test_l2_regularized_loss_strongly_convex_grad():
    """grad difference inner product >= mu ||x-y||^2 spot check."""
    params_a = init_logreg(seed=0)
    params_b = init_logreg(seed=1)
    ds = make_classification(n_samples=64, seed=0)
    batch = (jnp.asarray(ds.x), jnp.asarray(ds.y))
    mu = 0.05
    loss = lambda p: l2_regularized_loss(logreg_apply, p, batch, mu=mu)
    ga = jax.grad(loss)(params_a)
    gb = jax.grad(loss)(params_b)
    inner = sum(jnp.sum((x - y) * (u - v)) for x, y, u, v in zip(
        jax.tree.leaves(ga), jax.tree.leaves(gb),
        jax.tree.leaves(params_a), jax.tree.leaves(params_b)))
    sq = sum(jnp.sum((u - v) ** 2) for u, v in zip(
        jax.tree.leaves(params_a), jax.tree.leaves(params_b)))
    assert float(inner) >= mu * float(sq) - 1e-6


# ---------------------------------------------------------------------------
# Eval programs: accuracy and the test loss, one compiled program per call
# ---------------------------------------------------------------------------

MODELS = {"cnn": (init_cnn, cnn_apply), "mlp": (init_mlp, mlp_apply),
          "logreg": (init_logreg, logreg_apply)}


def _eager_hits(apply, params, x, y, batch):
    hits = 0
    for i in range(0, len(y), batch):
        logits = apply(params, x[i:i + batch])
        hits += int((jnp.argmax(logits, -1) == y[i:i + batch]).sum())
    return hits


@pytest.mark.parametrize("n", [1100, 1024])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_accuracy_matches_eager_per_batch_count(model, n):
    """1100 = 2 full batches of 512 and a remainder of 76; 1024 = 2."""
    init, apply = MODELS[model]
    ds = make_classification(n_samples=n, seed=5)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    params = init(seed=3)
    assert accuracy(apply, params, x, y, batch=512) == (
        _eager_hits(apply, params, x, y, 512) / n)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_l2_regularized_loss_matches_unjitted(model):
    init, apply = MODELS[model]
    ds = make_classification(n_samples=32, seed=6)
    batch = (jnp.asarray(ds.x), jnp.asarray(ds.y))
    params = init(seed=4)
    plain = l2_regularized_loss.__wrapped__
    np.testing.assert_allclose(
        float(l2_regularized_loss(apply, params, batch, mu=0.05)),
        float(plain(apply, params, batch, mu=0.05)), rtol=1e-6, atol=1e-6)
    g = jax.grad(lambda p: l2_regularized_loss(apply, p, batch, mu=0.05))(
        params)
    g0 = jax.grad(lambda p: plain(apply, p, batch, mu=0.05))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_eval_second_call_compiles_nothing():
    """The first call of each eval function compiles one program; a
    second with the same ``apply_fn`` and shapes compiles nothing."""
    ds = make_classification(n_samples=333, seed=7)   # shapes of this test
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    params = init_cnn(seed=2)

    def eval_once():
        return (accuracy(cnn_apply, params, x, y, batch=128),
                float(l2_regularized_loss(cnn_apply, params, (x, y))))

    first, second = spans.Recorder(), spans.Recorder()
    with spans.recording(first):
        a = eval_once()
    with spans.recording(second):
        b = eval_once()
    assert a == b
    compiles = {fun: int(count) for (event, fun), (_, count)
                in first.counters.items()
                if event == "/jax/core/compile/backend_compile_duration"}
    assert compiles == {"jit(_accuracy_hits)": 1,
                        "jit(l2_regularized_loss)": 1}
    assert second.counters == {}


def _opcodes(hlo_text):
    return collections.Counter(
        m.group(1) for m in re.finditer(
            r"^\s*(?:ROOT\s+)?%[\w.\-]+ = .*?\s([a-z][\w\-]*)\(",
            hlo_text, re.MULTILINE))


def test_round_program_with_jitted_loss_does_the_same_work():
    """The round program inlines the jitted loss: the same HLO ops and
    FLOPs as a round built on the plain function."""
    n, T, B, hw = 4, 2, 8, 12
    params = init_cnn(seed=0, image_hw=hw)
    batches = (jnp.zeros((n, T, B, hw, hw, 1)),
               jnp.zeros((n, T, B), jnp.int32))
    args = (params, batches, jnp.full((n, n), 1.0 / n), jnp.ones(n),
            jnp.float32(n), jnp.float32(0.1))
    compiled = {}
    for name, loss in (("jit", l2_regularized_loss),
                       ("plain", l2_regularized_loss.__wrapped__)):
        round_fn = make_round_fn(partial(loss, cnn_apply, mu=1e-2),
                                 mixing_backend="einsum")
        compiled[name] = round_fn.lower(*args).compile()
    assert _opcodes(compiled["jit"].as_text()) == _opcodes(
        compiled["plain"].as_text())
    assert compiled["jit"].cost_analysis()["flops"] == compiled[
        "plain"].cost_analysis()["flops"]
