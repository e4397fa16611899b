"""Pallas TPU flash-attention (forward) with GQA, causal and sliding-window
masking.

Design (TPU-native, not a CUDA port):

* grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is innermost --
  Pallas TPU executes the grid sequentially per core, so the online-softmax
  state (m, l, acc) lives in VMEM scratch that persists across kv steps and
  is re-initialized at kv_block == first.
* BlockSpecs tile Q/O as (bq, hd) and K/V as (bk, hd) VMEM blocks; the GQA
  group mapping happens in the K/V index_map (kv head = q head // group),
  so no KV duplication is materialized -- the MXU reads the same KV tile
  for all heads of a group.
* fully-masked kv blocks (beyond the causal diagonal or outside the
  sliding window) are skipped with pl.when -- for long_500k-style windows
  this turns O(S^2) into O(S * window) work.
* numerics: scores/softmax accumulate in f32 (MXU native), output cast to
  the input dtype on the final kv step.

Validated in interpret mode against ``ref.attention_ref`` over shape/dtype
sweeps (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, bq: int, bk: int, n_kv_blocks: int,
                  causal: bool, window: Optional[int], seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    q_start = qi * bq
    k_start = ki * bk

    # --- block-level skip decisions (static per (qi, ki) would be ideal;
    # they are cheap scalar tests evaluated on-core) ---
    oob = k_start >= seq_k                      # kv padding block
    if causal:
        oob |= k_start > q_start + bq - 1
    if window is not None:
        # oldest query in this block is q_start; its oldest visible key is
        # q_start - (window - 1).  The kv block is dead only if it lies
        # entirely before that.
        oob |= (k_start + bk - 1) < q_start - (window - 1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_not(oob))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)     # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)     # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)     # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = cols < seq_k
        if causal:
            ok &= cols <= rows
        if window is not None:
            ok &= (rows - cols) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (all NEG_INF) from exp overflow of -inf diffs
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)         # dead rows (padding) -> 0 out
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: Optional[int] = None,
                           true_seq_k: Optional[int] = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = True) -> jnp.ndarray:
    """q (B,Hq,Sq_pad,hd), k/v (B,Hkv,Sk_pad,hd) -- pre-padded to block
    multiples by ops.py.  ``true_seq_k`` masks the kv padding tail."""
    B, Hq, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    group = Hq // Hkv
    n_q_blocks = Sq // bq
    n_kv_blocks = Sk // bk
    grid = (B, Hq, n_q_blocks, n_kv_blocks)

    kernel = functools.partial(
        _flash_kernel, scale=hd ** -0.5, bq=bq, bk=bk,
        n_kv_blocks=n_kv_blocks, causal=causal, window=window,
        seq_k=true_seq_k if true_seq_k is not None else Sk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
