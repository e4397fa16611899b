"""The program's StableLM-2 block against the plain reference
(``bench/reference/fl_lm.py``) at a small size on the CPU: logits, loss
and every gradient leaf agree on seeded weights, and each planted
departure from the published block (the LayerNorm shift left out, the
q/k/v biases left out, rotary over the whole head) fails the same
tolerances.  One test pins the program's ``stablelm-1.6b`` to the
published config."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import fl_lm
from repro.configs import get_config
from repro.models import layers
from repro.models.model import Model

# 2 layers, d_model 256, 4 heads of 64 (rotary over the first 16 dims),
# d_ff 704, vocab 1024, 64 tokens
SMALL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
             d_ff=704, vocab_size=1024, dtype="float32", remat=False)
S = 64

# Both sides run float32 at "highest" on the CPU and differ only in the
# order of their sums.  Measured at this size: logits 1.1e-6 and the
# worst gradient leaf 1.4e-6 of their largest entry, the loss 6.5e-8 of
# itself; the tolerances leave 10-15x of room.  Each planted departure
# below moves the logits and the gradients by 0.65 or more, and the loss
# by 1.3e-3 or more.
LOGITS_TOL = 1e-5
LOSS_TOL = 1e-6
GRAD_TOL = 2e-5


def _config(**changes):
    return dataclasses.replace(get_config("stablelm-1.6b"),
                               **{**SMALL, **changes})


def _model_block(cfg):
    """The published keys the reference reads, from a program config."""
    return {"hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "partial_rotary_factor": 0.25,
            "layer_norm_eps": 1e-5, "rope_theta": 10000.0}


def _seeded_params(cfg, seed=0):
    """The program's init with every leaf redrawn, so the LayerNorm scales
    and shifts and the biases are not ones and zeros."""
    params = Model(cfg).init(jax.random.key(seed))
    leaves, tree = jax.tree.flatten_with_path(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    out = []
    for k, (path, leaf) in zip(keys, leaves):
        name = jax.tree_util.keystr(path)
        noise = jax.random.normal(k, leaf.shape, leaf.dtype)
        if name.endswith("['scale']"):
            out.append(1.0 + 0.2 * noise)
        elif name.endswith("['bias']") or name.endswith("['b']"):
            out.append(0.2 * noise)
        else:
            out.append(leaf)
    return jax.tree.unflatten(tree, out)


def _window(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (2, S + 1)),
                       jnp.int32)


def _program(cfg, params, window):
    model = Model(cfg)
    batch = (window[:, :-1], window[:, 1:])
    logits, _ = model.forward(params, batch[0])
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    return logits, loss, grads


def _reference(cfg, params, window):
    arch = fl_lm.Arch.of(_model_block(cfg))
    logits = fl_lm.logits(arch, params, window[:, :-1])
    loss, grads = jax.value_and_grad(
        lambda p: fl_lm.loss(arch, p, window))(params)
    return logits, loss, grads


def _by_path(tree):
    return [(jax.tree_util.keystr(k), v)
            for k, v in jax.tree.leaves_with_path(tree)]


def _errors(cfg, params, program_params, program_cfg):
    """(logits, loss, worst gradient leaf) relative errors of the program
    at ``program_cfg`` and ``program_params`` against the reference at
    ``cfg`` and ``params``."""
    window = _window(cfg)
    with jax.default_matmul_precision("highest"):
        pl, ploss, pg = _program(program_cfg, program_params, window)
        rl, rloss, rg = _reference(cfg, params, window)
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b))  # noqa: E731
                             / jnp.max(jnp.abs(b)))
    ref_grads = dict(_by_path(rg))
    grad = max(rel(g, ref_grads[k]) for k, g in _by_path(pg))
    return rel(pl, rl), abs(float(ploss - rloss)) / abs(float(rloss)), grad


def _fails(errors):
    logits, loss, grad = errors
    return logits > LOGITS_TOL or loss > LOSS_TOL or grad > GRAD_TOL


def test_block_matches_the_reference():
    cfg = _config()
    params = _seeded_params(cfg)
    window = _window(cfg)
    with jax.default_matmul_precision("highest"):
        pl, ploss, pg = _program(cfg, params, window)
        rl, rloss, rg = _reference(cfg, params, window)
    assert pl.shape == rl.shape == (2, S, cfg.vocab_size)
    scale = float(jnp.max(jnp.abs(rl)))
    np.testing.assert_allclose(np.asarray(pl), np.asarray(rl),
                               rtol=0, atol=LOGITS_TOL * scale)
    assert abs(float(ploss - rloss)) <= LOSS_TOL * abs(float(rloss))
    assert jax.tree.structure(pg) == jax.tree.structure(rg)
    for (path, a), b in zip(jax.tree.leaves_with_path(pg),
                            jax.tree.leaves(rg)):
        b = np.asarray(b)
        assert np.max(np.abs(b)) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=0, atol=GRAD_TOL * np.max(np.abs(b)),
            err_msg=jax.tree_util.keystr(path))


def _no_shift(cfg, params, monkeypatch):
    real = layers.layer_norm
    monkeypatch.setattr(layers, "layer_norm",
                        lambda x, scale, bias, eps: real(x, scale, 0 * bias,
                                                         eps))
    return cfg, params


def _no_qkv_bias(cfg, params, monkeypatch):
    params = jax.tree.map(lambda a: a, params)
    for name in ("q", "k", "v"):
        del params["decoder"]["layers"]["attn"][name]["b"]
    return cfg, params


def _full_rotary(cfg, params, monkeypatch):
    return dataclasses.replace(cfg, rope_fraction=1.0), params


@pytest.mark.parametrize("plant", [_no_shift, _no_qkv_bias, _full_rotary],
                         ids=["no_layernorm_shift", "no_qkv_bias",
                              "full_rotary"])
def test_planted_departure_fails_the_tolerances(plant, monkeypatch):
    """The program with the departure against the published reference."""
    cfg = _config()
    params = _seeded_params(cfg)
    program_cfg, program_params = plant(cfg, params, monkeypatch)
    errors = _errors(cfg, params, program_params, program_cfg)
    assert _fails(errors), errors


def test_stablelm_is_the_published_config():
    cfg = get_config("stablelm-1.6b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == (
        24, 2048, 32, 32, 64, 5632, 100352)
    assert cfg.qkv_bias and not cfg.qk_norm and not cfg.tie_embeddings
    assert cfg.norm_type == "layer" and cfg.norm_eps == 1e-5
    assert cfg.rope_fraction == 0.25 and cfg.rope_theta == 10_000.0
    assert cfg.mlp_type == "swiglu" and cfg.sliding_window is None
    params = jax.eval_shape(Model(cfg).init, jax.random.key(0))
    layer = params["decoder"]["layers"]
    for norm in (layer["ln1"], layer["ln2"], params["final_norm"]):
        assert set(norm) == {"scale", "bias"}
    assert all("b" in layer["attn"][k] for k in ("q", "k", "v"))
    assert "b" not in layer["attn"]["o"]
    assert cfg.param_count() == 1_644_515_328
