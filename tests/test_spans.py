"""Program spans and compile counters (``repro.spans``) and where the FL
round carries them: nothing is kept and no listener stays registered
without a recorder; spans nest; the compile events count only while a
recorder is attached; ``FederatedServer.run`` records its layers in
order; the round program names its local SGD, mix and global update."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro import spans
from repro.core import D2DNetwork, FederatedServer, ServerConfig
from repro.core.rounds import make_round_fn
from repro.data import FederatedBatcher, label_sorted_partition, \
    make_classification
from repro.fl import RoundPlan


def _listeners():
    return len(monitoring.get_event_duration_listeners())


def test_without_a_recorder_nothing_is_kept():
    before = _listeners()
    late = spans.Recorder()
    with spans.recording(late):
        pass
    with spans.span("outer", round=3):
        with spans.span("inner"):
            jax.jit(lambda x: x * 3.0 - 1.0)(jnp.ones(5)).block_until_ready()
    assert spans._recorder is None
    assert _listeners() == before
    assert late.spans == [] and late.counters == {}


def test_spans_nest_with_parent_and_round():
    rec = spans.Recorder()
    with spans.recording(rec):
        with spans.span("outer", round=7):
            with spans.span("inner", round=8):
                pass
            with spans.span("sibling"):
                pass
    by_name = {r.name: r for r in rec.spans}
    assert [r.name for r in rec.spans] == ["inner", "sibling", "outer"]
    assert by_name["outer"].parent is None and by_name["outer"].round == 7
    assert by_name["inner"].parent == "outer" and by_name["inner"].round == 8
    assert by_name["sibling"].parent == "outer"
    assert by_name["sibling"].round is None
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_a_span_that_raises_is_still_recorded():
    rec = spans.Recorder()
    with spans.recording(rec):
        with pytest.raises(ValueError):
            with spans.span("fails"):
                raise ValueError("no")
        with spans.span("after"):
            pass
    assert [(r.name, r.parent) for r in rec.spans] == [("fails", None),
                                                        ("after", None)]


def test_compile_counters_fill_only_while_recording():
    before = _listeners()
    rec = spans.Recorder()

    def fresh_program(x):
        return jnp.sin(x) * 2.0 + x

    with spans.recording(rec):
        assert _listeners() == before + 1
        jax.jit(fresh_program)(jnp.arange(7.0)).block_until_ready()
    assert _listeners() == before
    events = {event for event, _ in rec.counters}
    assert set(spans.COMPILE_EVENTS[:3]) <= events
    compiled = rec.counters[("/jax/core/compile/backend_compile_duration",
                             "jit(fresh_program)")]
    assert compiled[0] > 0 and compiled[1] == 1
    assert all(s >= 0 and n >= 1 for s, n in rec.counters.values())
    counted = dict(rec.counters)
    jax.jit(lambda x: x - 4.0)(jnp.arange(9.0)).block_until_ready()
    assert rec.counters == counted


def test_program_counts_fill_only_while_recording():
    rec = spans.Recorder()
    spans.count("engine.program_cache", "miss")
    with spans.recording(rec):
        spans.count("engine.program_cache", "miss")
        spans.count("engine.program_cache", "hit")
        spans.count("engine.program_cache", "hit")
    spans.count("engine.program_cache", "hit")
    assert rec.counters == {("engine.program_cache", "miss"): [0.0, 1],
                            ("engine.program_cache", "hit"): [0.0, 2]}
    assert rec.spans == []


def _linear_loss(params, batch):
    x, y = batch
    logits = x.reshape(x.shape[0], -1) @ params["w"]
    return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(len(y)), y])


def test_server_run_records_each_layer_per_segment():
    n, K, T = 6, 3, 2
    ds = make_classification(n_samples=240, n_classes=4, image_hw=4, seed=1)
    parts = label_sorted_partition(ds, n, rng=np.random.default_rng(2))
    net = D2DNetwork(n=n, c=2, k_range=(2, 2), p_fail=0.0)
    cfg = ServerConfig(T=T, t_max=K, phi_max=0.5, seed=4,
                       eta=lambda t: 0.1)
    server = FederatedServer(
        net, _linear_loss, {"w": jnp.zeros((16, 4))},
        FederatedBatcher(ds, parts, T=T, batch_size=3), cfg)
    rec = spans.Recorder()
    with spans.recording(rec):
        for segment in range(2):
            plan = RoundPlan.connectivity_aware(
                net, ServerConfig(T=T, t_max=K, phi_max=0.5, seed=segment))
            server.run(eval_fn=lambda p: {"w2": float(jnp.sum(p["w"] ** 2))},
                       plan=plan)
    runs = [r for r in rec.spans if r.name == "server.run"]
    assert len(runs) == 2
    for run in runs:
        inside = sorted((r for r in rec.spans if r.parent == "server.run"
                         and run.t0 <= r.t0 and r.t1 <= run.t1),
                        key=lambda r: r.t0)
        assert [(r.name, r.round) for r in inside] == (
            [("server.batches", None), ("engine.prepare", None)]
            + [(name, t) for t in range(K)
               for name in ("engine.dispatch", "engine.eval")])
        batches = [r for r in rec.spans if r.name == "data.batch"
                   and run.t0 <= r.t0 and r.t1 <= run.t1]
        assert len(batches) == K
        assert {r.parent for r in batches} == {"server.batches"}
        gathers = [r for r in rec.spans if r.name == "data.gather"
                   and run.t0 <= r.t0 and r.t1 <= run.t1]
        assert len(gathers) == K
        assert {r.parent for r in gathers} == {"data.batch"}
    # the training set goes to the device once, in the first draw
    uploads = [r for r in rec.spans if r.name == "data.upload"]
    assert [r.parent for r in uploads] == ["data.batch"]
    assert runs[0].t0 <= uploads[0].t0 and uploads[0].t1 <= runs[0].t1
    assert [r.parent for r in rec.spans if r.name == "plan.build"] == [
        None, None]


@pytest.mark.parametrize("backend", ["einsum", "aggregate"])
def test_round_program_names_local_sgd_mix_and_global_update(backend):
    n, T, B, d = 4, 2, 3, 5
    round_fn = make_round_fn(
        lambda p, b: jnp.mean((b[0] @ p["w"]) ** 2),
        mixing_backend=backend)
    text = round_fn.lower(
        {"w": jnp.zeros((d, 2))}, (jnp.ones((n, T, B, d)),), jnp.eye(n),
        jnp.ones(n), jnp.float32(n), jnp.float32(0.1)).as_text(
            debug_info=True)
    assert "jit(round_fn)/local_sgd/" in text
    assert "jit(round_fn)/mix/" in text
    assert "/mix/global_update/" in text
