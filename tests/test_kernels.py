"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
with hypothesis sweeps over shapes and dtypes (per assignment)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.fl import packing
from repro.kernels.mixing.ops import mix_aggregate_grouped
from repro.kernels.mixing.ref import mix_ref
from repro.core import D2DNetwork, network_matrix


# ---------------------------------------------------------------------------
# Graph-mixing kernel: the mixed leg of ``mix_aggregate_grouped``
# ---------------------------------------------------------------------------

def _mix(A, X, chunk=2048):
    n = A.shape[0]
    (mixed,), _ = mix_aggregate_grouped(A, jnp.ones(n), jnp.float32(n),
                                        (X,), chunk=chunk)
    return mixed


@given(st.integers(2, 40), st.integers(1, 5000),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 2**31))
@settings(max_examples=25, deadline=None)
def test_mixing_kernel_matches_ref(n, p, dtype, seed):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    got = _mix(A, X, chunk=512)
    want = mix_ref(A, X)
    assert got.dtype == X.dtype
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_mixing_kernel_column_stochastic_preserves_sum():
    """With a real equal-neighbor matrix the kernel must preserve the delta
    sum (the average-preserving property the algorithm relies on)."""
    rng = np.random.default_rng(0)
    net = D2DNetwork(n=32, c=2, p_fail=0.15)
    A = jnp.asarray(network_matrix(net.sample(rng), 32), jnp.float32)
    X = jnp.asarray(rng.standard_normal((32, 4097)), jnp.float32)
    out = _mix(A, X)
    np.testing.assert_allclose(np.asarray(out.sum(0)), np.asarray(X.sum(0)),
                               rtol=1e-4, atol=1e-4)


def test_mixing_pytree_matches_tree_einsum():
    rng = np.random.default_rng(1)
    n = 12
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    deltas = {"w": jnp.asarray(rng.standard_normal((n, 33, 7)), jnp.float32),
              "b": jnp.asarray(rng.standard_normal((n, 129)), jnp.float32)}
    spec = packing.pack_spec(deltas)
    (mixed,), _ = mix_aggregate_grouped(A, jnp.ones(n), jnp.float32(n),
                                        packing.pack(deltas, spec))
    got = packing.unpack((mixed,), spec)
    for key in deltas:
        flat = deltas[key].reshape(n, -1)
        want = mix_ref(A, flat).reshape(deltas[key].shape)
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

def _qkv(rng, B, S, Hq, Hkv, hd, dtype):
    q = jnp.asarray(rng.standard_normal((B, S, Hq, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, hd)), dtype)
    return q, k, v


@given(st.sampled_from([(1, 128, 4, 4, 64), (2, 256, 4, 2, 32),
                        (1, 130, 8, 1, 64), (1, 64, 2, 2, 128),
                        (2, 200, 6, 3, 32)]),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 2**31))
@settings(max_examples=20, deadline=None)
def test_flash_matches_ref_causal(shape, dtype, seed):
    B, S, Hq, Hkv, hd = shape
    rng = np.random.default_rng(seed)
    q, k, v = _qkv(rng, B, S, Hq, Hkv, hd, dtype)
    got = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [16, 64, 100])
def test_flash_matches_ref_sliding_window(window):
    rng = np.random.default_rng(7)
    q, k, v = _qkv(rng, 1, 192, 4, 2, 64, jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 2, 128, 2, 2, 32, jnp.float32)
    got = flash_attention(q, k, v, causal=False, bq=64, bk=64)
    want = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_agrees_with_model_attention_path():
    """End-to-end: model attention with attn_impl='flash' == 'ref'."""
    import dataclasses
    from repro.models import attention as attn_mod
    from repro.models.config import ModelConfig

    cfg_ref = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=7,
                          head_dim=16, attn_impl="ref")
    cfg_fl = dataclasses.replace(cfg_ref, attn_impl="flash")
    p = attn_mod.attn_init(jax.random.key(0), cfg_ref, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 96, 64))
    pos = jnp.arange(96)
    y_ref = attn_mod.attention_full(cfg_ref, p, x, pos)
    y_fl = attn_mod.attention_full(cfg_fl, p, x, pos)
    np.testing.assert_allclose(np.asarray(y_fl), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
