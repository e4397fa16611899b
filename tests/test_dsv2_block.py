"""DeepSeek-V2's block pieces at small seeded sizes on the CPU: YaRN's
frequencies and softmax scale against a numpy transcription of the
published formulas, the raw top-k gates, the expert-share identity (the
shares' routed parts and the shared expert once add up to the uncut
layer), the ragged share under ``scan`` and ``grad`` against the dense
oracle, the rope pairing the config file's departures name, and the
registry's published keys."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import mla, moe
from repro.models.config import ModelConfig
from repro.models.layers import rope_angles, yarn_inv_freq, yarn_mscale


def _yarn_numpy(dim=64, theta=1e4, factor=40.0, original=4096,
                beta_fast=32, beta_slow=1):
    """The formulas as written: i = 0..dim/2-1."""
    i = np.arange(dim // 2)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / factor
    corr = lambda b: dim * np.log(original / (2 * np.pi * b)) / (  # noqa
        2 * np.log(theta))
    low, high = math.floor(corr(beta_fast)), math.ceil(corr(beta_slow))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp), low, high


def test_yarn_frequencies_and_scale_follow_the_formulas():
    want, low, high = _yarn_numpy()
    assert (low, high) == (10, 23)
    got = np.asarray(yarn_inv_freq(64, 1e4, 40.0, 4096, 32.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # dims below low keep the plain frequency, dims above high are / 40
    np.testing.assert_allclose(got[:low + 1], 1e4 ** (-np.arange(11) / 32),
                               rtol=1e-6)
    np.testing.assert_allclose(got[high:], want[high:], rtol=1e-6)
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    # the cos/sin factor mscale / mscale_all_dim is 1 as published
    pos = jnp.arange(7)
    cos, sin = mla._rope(cfg, pos)
    c2, s2 = rope_angles(pos, 64, 1e4, jnp.asarray(want, jnp.float32))
    np.testing.assert_allclose(np.asarray(cos), np.asarray(c2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(s2), atol=1e-6)


def test_plain_rope_is_unchanged_without_yarn():
    cfg = get_config("deepseek-v2-236b")
    assert cfg.yarn_factor == 0
    assert mla.softmax_scale(cfg) == 192 ** -0.5
    pos = jnp.arange(5)
    for a, b in zip(mla._rope(cfg, pos), rope_angles(pos, 64, 1e4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _moe_cfg(**kw):
    base = dict(name="t", family="moe", n_layers=2, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=48, vocab_size=64, n_experts=16,
                experts_per_token=6, n_shared_experts=2, moe_d_ff=24,
                norm_topk_prob=False, moe_impl="dense")
    base.update(kw)
    return ModelConfig(**base)


def _x(seed, T=40, d=32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, T, d)), jnp.float32)


def test_raw_gates_are_the_softmax_probabilities():
    cfg = _moe_cfg()
    p = moe.moe_init(jax.random.key(0), cfg, jnp.float32)
    xf = _x(0)[0]
    w, ids, _ = moe._route(cfg, p, xf)
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.take_along_axis(np.asarray(probs),
                                          np.asarray(ids), axis=-1),
        rtol=1e-6)
    assert float(jnp.max(jnp.sum(w, axis=-1))) < 1.0
    renorm, _, _ = moe._route(dataclasses.replace(cfg, norm_topk_prob=True),
                              p, xf)
    np.testing.assert_allclose(np.asarray(jnp.sum(renorm, -1)), 1.0,
                               rtol=1e-6)
    scaled, _, _ = moe._route(dataclasses.replace(cfg, routed_scaling=16.0),
                              p, xf)
    np.testing.assert_allclose(np.asarray(scaled), 16 * np.asarray(w),
                               rtol=1e-6)


def _share(p, cfg, offset, held):
    sl = slice(offset, offset + held)
    q = {k: p[k][sl] for k in ("gate", "up", "down")}
    q["router"] = p["router"]
    q["shared"] = p["shared"]
    return q, dataclasses.replace(cfg, experts_held=held,
                                  expert_offset=offset)


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_expert_shares_add_up_to_the_uncut_layer(impl):
    """Shares of 4 experts at offsets 0, 4, 8, 12: their outputs less the
    shared expert that each computes alike, plus the shared expert once,
    equal the uncut layer; each share's aux loss is the uncut one."""
    cfg = _moe_cfg(moe_impl=impl)
    p = moe.moe_init(jax.random.key(1), cfg, jnp.float32)
    x = _x(1)
    with jax.default_matmul_precision("highest"):
        whole, aux = moe.moe_apply(cfg, p, x)
        shared = moe.mlp_apply(p["shared"], x, "swiglu")
        parts = []
        for off in range(0, 16, 4):
            q, c = _share(p, cfg, off, 4)
            y, a = moe.moe_apply(c, q, x)
            parts.append(y - shared)
            assert float(a) == pytest.approx(float(aux), rel=1e-6)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)


def test_ragged_share_under_scan_and_grad_matches_dense():
    """The ragged share (held slots sorted first, the rest zeroed) gives
    the dense oracle's output and gradients through a scan."""
    cfg = _moe_cfg(experts_held=4, expert_offset=8)
    p = moe.moe_init(jax.random.key(2), cfg, jnp.float32)
    assert p["gate"].shape[0] == 4 and p["router"].shape[1] == 16
    xs = jnp.stack([_x(s) for s in range(3)])

    def total(params, impl):
        c = dataclasses.replace(cfg, moe_impl=impl)

        def body(acc, x):
            y, aux = moe.moe_apply(c, params, x)
            return acc + jnp.sum(jnp.sin(y)) + aux, None

        return jax.lax.scan(body, jnp.float32(0.0), xs)[0]

    with jax.default_matmul_precision("highest"):
        got, g = jax.value_and_grad(total)(p, "ragged")
        want, h = jax.value_and_grad(total)(p, "dense")
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(h)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)
        assert np.all(np.isfinite(np.asarray(a)))


def _interleaved_rope(x, inv_freq):
    """The published pairing: view the rope dims as (d/2, 2) pairs
    (2i, 2i+1), de-interleave, then rotate halves."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    cos, sin = (jnp.concatenate([c, c], -1) for c in (cos, sin))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def test_rope_pairing_agrees_under_the_column_permutation():
    """The program's rotate-half on weights W equals the published
    (2i, 2i+1) pairing on weights whose rope columns are W's permuted by
    [0, 2, ..., 62, 1, 3, ..., 63] placed back: the attention scores of
    q_pe k_pe agree."""
    dr, S, H = 64, 9, 2
    rng = np.random.default_rng(4)
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    q = jnp.asarray(rng.standard_normal((1, S, H, dr)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, S, 1, dr)), jnp.float32)
    inv = jnp.asarray(_yarn_numpy()[0], jnp.float32)
    cos, sin = rope_angles(jnp.arange(S), dr, 1e4, inv)

    def half(x):
        c, s = cos[None, :, None], sin[None, :, None]
        x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    ours = jnp.einsum("bshd,btd->bhst", half(q), half(k)[:, :, 0])
    # the published layout: column perm[j] of W_pub is column j of W
    q_pub = jnp.zeros_like(q).at[..., perm].set(q)
    k_pub = jnp.zeros_like(k).at[..., perm].set(k)
    pub = jnp.einsum("bshd,btd->bhst", _interleaved_rope(q_pub, inv),
                     _interleaved_rope(k_pub, inv)[:, :, 0])
    np.testing.assert_allclose(np.asarray(ours), np.asarray(pub),
                               rtol=1e-5, atol=1e-5)


def test_registry_states_the_published_gates():
    lite = get_config("deepseek-v2-lite")
    assert (lite.n_layers, lite.d_model, lite.n_heads, lite.vocab_size) == \
        (27, 2048, 16, 102400)
    assert (lite.kv_lora_rank, lite.q_lora_rank, lite.nope_head_dim,
            lite.rope_head_dim, lite.v_head_dim) == (512, 0, 128, 64, 128)
    assert (lite.n_experts, lite.experts_per_token, lite.n_shared_experts,
            lite.moe_d_ff, lite.first_dense_layers, lite.d_ff) == \
        (64, 6, 2, 1408, 1, 10944)
    assert (lite.norm_topk_prob, lite.routed_scaling, lite.norm_eps) == \
        (False, 1.0, 1e-6)
    assert (lite.yarn_factor, lite.yarn_original_max_pos,
            lite.yarn_mscale, lite.yarn_mscale_all_dim) == \
        (40.0, 4096, 0.707, 0.707)
    big = get_config("deepseek-v2-236b")
    assert (big.norm_topk_prob, big.routed_scaling) == (False, 16.0)
    # the defaults leave every other config as it was
    for name in ("qwen3-32b", "phi3.5-moe-42b-a6.6b", "stablelm-1.6b"):
        c = get_config(name)
        assert (c.norm_topk_prob, c.routed_scaling, c.experts_held,
                c.yarn_factor) == (True, 1.0, 0, 0.0)
