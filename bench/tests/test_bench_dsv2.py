"""``dsv2lite-n4`` on the CPU: a tiny cut of the cell through
``runner.run_cell`` on one host device, with the engine's memory made
small so that it takes the sequential cohort form as on the chip.  The
cut is correct, each planted fault is not, the engine counts its form,
and the reader compiles the program the clients trained in.  Also: the
program's loss and gradients against the reference's, the FLOP count,
and set-up refusing a block that is not the published one."""

import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny  # noqa: F401  (puts the checkout on the path)
from bench.harness import cells, runner
from bench.harness.peaks import PEAKS
from bench.harness.spans import Spans
from bench.reference import fl_dsv2
from bench.systems import fl_lm_local
from repro import spans
from repro.fl import engine

CELL = "dsv2lite-n4"
SEED = 2 ** 31 + 29


def tiny_dsv2_cell():
    cell = cells.resolve(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg.update(num_hidden_layers=3, hidden_size=64, intermediate_size=96,
               num_attention_heads=2, num_key_value_heads=2,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, moe_intermediate_size=32,
               router_experts=16, n_routed_experts=4, expert_offset=4,
               vocab_size=256)
    cfg["population"].update(train_tokens=8192)
    cfg["training"].update(T=2, eta=0.05)
    traffic = dict(cell.traffic, seq_len=32, segment_rounds=3)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


@pytest.fixture
def small_device(monkeypatch):
    """A device too small for the vmapped cohort."""
    monkeypatch.setattr(engine, "_bytes_limit", lambda: 1 << 20)


def _run(cell):
    return runner.run_cell(cell, SEED, 0.0, False, jax.devices()[:1],
                           PEAKS["TPU v5 lite"], time.perf_counter())


def _wrap_round(monkeypatch, wrap):
    """Each round the engine builds runs through ``wrap(round_fn)``."""
    real = engine.make_round_fn
    monkeypatch.setattr(engine, "make_round_fn",
                        lambda *a, **k: wrap(real(*a, **k)))


def _frozen(monkeypatch):
    _wrap_round(monkeypatch, lambda r: lambda params, *a, **k: (params,
                                                                 None))


def _half_batch(monkeypatch):
    def wrap(r):
        def half(params, batches, *a, **k):
            cut = batches[0].shape[-1] // 2
            return r(params, tuple(b[..., :cut] for b in batches), *a, **k)
        return half
    _wrap_round(monkeypatch, wrap)


def _no_mixing(monkeypatch):
    def wrap(r):
        def unmixed(params, batches, A, *a, **k):
            return r(params, batches, jnp.eye(A.shape[0], dtype=A.dtype),
                     *a, **k)
        return unmixed
    _wrap_round(monkeypatch, wrap)


def _model_fault(**changes):
    def plant(monkeypatch):
        real = fl_lm_local.model_config
        monkeypatch.setattr(fl_lm_local, "model_config",
                            lambda cfg: dataclasses.replace(real(cfg),
                                                            **changes))
    return plant


FAULTS = {"frozen": _frozen, "half_batch": _half_batch,
          "no_mixing": _no_mixing,
          "renorm_topk": _model_fault(norm_topk_prob=True),
          "no_yarn": _model_fault(yarn_factor=0.0)}


def test_tiny_cut_is_correct_in_the_sequential_form(small_device):
    rec = spans.Recorder()
    with spans.recording(rec):
        result = _run(tiny_dsv2_cell())
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == fl_lm_local.CHECK_ROUNDS
    assert set(result["checks"]) == {"loss_gap", "update_gap", "change_gap",
                                     "change_err", "comm_mismatch"}
    assert {"round_ms", "setup_s"} <= set(result["metrics"])
    # set-up's segment and the window's one, each one execute
    assert rec.counters[("engine.cohort", "sequential")] == [0.0, 2]
    assert ("engine.cohort", "vmap") not in rec.counters


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(small_device, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    result = _run(tiny_dsv2_cell())
    assert result["correct"] is False, result["checks"]
    assert result["failed"] == result["attempted"]


def _instructions(text):
    from bench.metrics.local_sgd_ms import _INSTRUCTION, result_shape

    return {m.group(1): result_shape(m.group(2))
            for m in map(_INSTRUCTION.match, text.splitlines()) if m}


def test_reader_compiles_the_program_the_clients_trained_in(
        small_device, monkeypatch):
    ran = {}

    def wrap(r):
        def first_call(params, batches, A, tau, m, eta, *rest):
            ran.setdefault("text", r.accumulate.lower(
                params, batches, A, tau, m, eta).compile().as_text())
            return r(params, batches, A, tau, m, eta, *rest)
        return first_call

    _wrap_round(monkeypatch, wrap)
    cell = tiny_dsv2_cell()
    system = cell.system().System(cell, SEED, Spans(), jax.devices())
    system.setup()
    text = system.train_step_text()
    assert _instructions(ran["text"]) == _instructions(text)
    from bench.metrics import local_sgd_ms
    from bench.metrics.moe_local_sgd_ms import scoped_instructions
    for scope in ("local_sgd", "mla", "moe_router", "moe_experts",
                  "moe_shared", "mix"):
        found = scoped_instructions(text, scope)
        assert found, scope
        assert set(local_sgd_ms.scoped_instructions(text, scope)) <= \
            set(found), scope
    # the dense first layer's attention is named under the transforms
    # (``jvp(mla)``, ``transpose(jvp(mla))``), not ``/mla/``: it counts
    under_jvp = [line for line in text.splitlines()
                 if "jvp(mla)" in line and "op_name=" in line]
    assert under_jvp
    named = scoped_instructions(text, "mla")
    assert any(local_sgd_ms._INSTRUCTION.match(line).group(1) in named
               for line in under_jvp if local_sgd_ms._INSTRUCTION.match(line))


def test_train_flops_from_shapes():
    m = cells.resolve(CELL).config
    d, H, ff, E = 2048, 16, 1408, 64
    mla = d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    moe_layer = d * E + 2 * 3 * d * ff + 3 * d * ff * 6 * 8 / 64
    matmul = 5 * mla + 3 * d * 10944 + 4 * moe_layer + d * 12800
    per_token = 6 * matmul + 5 * 6 * 2048 * H * (128 + 64 + 128)
    assert fl_lm_local.train_flops_per_token(m, 2048) == per_token
    # the round's 40,960 tokens: about 76.3 TFLOP
    assert per_token * 40960 == pytest.approx(76.28e12, rel=1e-3)


def _published_config_with(monkeypatch, **changes):
    from repro import configs

    real = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda name: dataclasses.replace(real(name),
                                                         **changes))


@pytest.mark.parametrize("changes", [
    {"norm_topk_prob": True}, {"yarn_factor": 0.0},
    {"routed_scaling": 16.0}, {"rope_theta": 1e6}],
    ids=["renorm", "no_yarn", "scaling", "theta"])
def test_setup_refuses_a_block_that_is_not_published(monkeypatch, changes):
    _published_config_with(monkeypatch, **changes)
    cell = tiny_dsv2_cell()
    with pytest.raises(ValueError, match="published"):
        cell.system().System(cell, 1, None, jax.devices())


def test_program_loss_and_grads_match_the_reference():
    """At the tiny cut in float32 on the CPU: the loss to 1e-5 and each
    gradient leaf to 1e-4 of its norm (float32 round-off over a few
    hundred terms; the ragged and dense expert paths sum in different
    orders)."""
    from repro.models.model import Model

    cfg = tiny_dsv2_cell().config
    mc = fl_lm_local.model_config(cfg)
    model = Model(mc)
    params = model.init(jax.random.key(3))
    rng = np.random.default_rng(3)
    window = jnp.asarray(rng.integers(0, mc.vocab_size, (2, 33)), jnp.int32)
    arch = fl_dsv2.Arch.of(cfg)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(model.loss)(
            params, (window[:, :-1], window[:, 1:]))
        want, ref_grads = jax.value_and_grad(
            lambda p: fl_dsv2.loss(arch, p, window))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        err = float(jnp.linalg.norm(g - r))
        assert err <= 1e-4 * float(jnp.linalg.norm(r)) + 1e-7, \
            jax.tree_util.keystr(path)


# the clients' program as a TPU compile prints it, cut to an attention
# op of a scanned layer, one of the dense layer, a grouped matmul's two
# ragged_dot kernels (no name path) and a combine op of the experts
GROUPED_HLO = '''
  %fusion.1 = f32[1,16,2048,2048]{3,2,1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(<unknown>)/while/body/closed_call/local_sgd/while/body/closed_call/jvp()/while/body/closed_call/mla/bshd,bthd->bhst/dot_general"}
  %fusion.2 = f32[1,16,2048,2048]{3,2,1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(<unknown>)/while/body/closed_call/local_sgd/while/body/closed_call/jvp(mla)/bshd,bthd->bhst/dot_general"}
  %ragged-dot-metadata = (s32[9]{0:T(128)}, s32[31]{0:T(128)}) custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none.1 = f32[12288,1408]{1,0:T(8,128)} custom-call(%d, %e), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %scatter.3 = f32[2048,2048]{1,0} scatter(%f, %g, %h), metadata={op_name="jit(<unknown>)/while/body/closed_call/local_sgd/while/body/closed_call/jvp()/while/body/closed_call/moe_experts/scatter-add"}
'''


@pytest.mark.parametrize("scope, ms", [
    ("mla", 3.0), ("moe_experts", 12.0), ("local_sgd", 15.0),
    ("moe_shared", None)])
def test_scope_readers_count_the_grouped_matmuls_in_the_experts(scope,
                                                                ms):
    """One round, each op its ``durations`` ms apart from the others: the
    attention ops count for ``mla`` (the dense layer's under ``jvp(mla)``
    too), the ragged_dot kernels for ``moe_experts`` and ``local_sgd``
    only, and a scope the program lacks reads nothing."""
    import types

    from bench.harness.trace import Op
    from bench.metrics import moe_local_sgd_ms

    durations = {"fusion.1": 1, "fusion.2": 2, "ragged-dot-metadata": 1,
                 "ragged-dot-none.1": 8, "scatter.3": 3}
    ops, start = [], 0
    for line in GROUPED_HLO.strip().splitlines():
        name, rest = line.strip().lstrip("%").split(" = ", 1)
        ops.append(Op(name, start, durations[name] * 1e6, line.strip()))
        start += 1e8
    ctx = types.SimpleNamespace(
        trace=types.SimpleNamespace(ops=ops),
        system=types.SimpleNamespace(train_step_text=lambda: GROUPED_HLO),
        window=types.SimpleNamespace(rounds=1))
    got = moe_local_sgd_ms.scope_ms(ctx, scope)
    assert got == (None if ms is None else pytest.approx(ms))
