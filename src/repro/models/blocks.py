"""Decoder blocks and scanned layer stacks for every architecture family.

Layer parameters are *stacked* (leading ``n_layers`` axis) and traversed
with ``jax.lax.scan`` so the HLO stays O(1) in depth -- essential for the
64-layer 32B dry-runs to lower/compile quickly.  Heterogeneous pieces live
outside the scan: DeepSeek's leading dense layer(s), and Zamba2's shared
attention block (applied every ``hybrid_attn_every`` mamba layers via a
grouped outer scan).

Each family provides three entry points used by ``model.py``:
  * ``stack_forward``  -- full-sequence training/scoring, returns aux loss
  * ``stack_prefill``  -- forward + per-layer cache entries (scan ys)
  * ``stack_decode``   -- one-token step threading per-layer cache slices
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mla as mla_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import mlp_apply, mlp_apply_sp, mlp_init, norm, norm_init
from .sharding import constrain_seq, sp_mlp_axis

PyTree = Any

__all__ = ["stack_init", "stack_forward", "stack_prefill", "stack_decode",
           "transformer_block_init", "mamba_block_init"]


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def transformer_block_init(key, cfg: ModelConfig, dtype,
                           is_moe: bool) -> PyTree:
    k1, k2 = jax.random.split(key)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm_type, dtype),
         "ln2": norm_init(cfg.d_model, cfg.norm_type, dtype)}
    if cfg.mla:
        p["mla"] = mla_mod.mla_init(k1, cfg, dtype)
    else:
        p["attn"] = attn.attn_init(k1, cfg, dtype)
    if is_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    return p


def mamba_block_init(key, cfg: ModelConfig, dtype) -> PyTree:
    return {"ln1": norm_init(cfg.d_model, cfg.norm_type, dtype),
            "ssm": ssm_mod.ssm_init(key, cfg, dtype)}


def _layer_is_moe(cfg: ModelConfig) -> bool:
    return cfg.n_experts > 0


def stack_init(key, cfg: ModelConfig, dtype) -> PyTree:
    """All decoder-layer parameters (embed/head live in model.py)."""
    out: PyTree = {}
    if cfg.family in ("ssm", "hybrid"):
        keys = jax.random.split(key, cfg.n_layers + 1)
        out["layers"] = jax.vmap(
            lambda k: mamba_block_init(k, cfg, dtype))(keys[:cfg.n_layers])
        if cfg.family == "hybrid":
            out["shared"] = transformer_block_init(keys[-1], cfg, dtype,
                                                   is_moe=False)
        return out

    n_scanned = cfg.n_layers - cfg.first_dense_layers
    keys = jax.random.split(key, cfg.n_layers)
    if cfg.first_dense_layers:
        dense_cfg_moe = False
        out["dense_layers"] = [
            transformer_block_init(keys[i], cfg, dtype, is_moe=dense_cfg_moe)
            for i in range(cfg.first_dense_layers)]
    out["layers"] = jax.vmap(
        lambda k: transformer_block_init(k, cfg, dtype,
                                         is_moe=_layer_is_moe(cfg))
    )(keys[cfg.first_dense_layers:])
    return out


# ---------------------------------------------------------------------------
# Per-layer forward
# ---------------------------------------------------------------------------

def _tf_block_forward(cfg: ModelConfig, p: PyTree, x, positions,
                      is_moe: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    x = constrain_seq(x)               # sequence parallelism (opt-in)
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    if cfg.mla:
        a = mla_mod.mla_full(cfg, p["mla"], h, positions)
    else:
        a = attn.attention_full(cfg, p["attn"], h, positions)
    x = constrain_seq(x + a)
    h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if is_moe:
        y, aux = moe_mod.moe_apply(cfg, p["moe"], h)
    else:
        ax = sp_mlp_axis()
        sp_ok = (ax is not None and cfg.mlp_type == "swiglu"
                 and h.ndim == 3)
        y = (mlp_apply_sp(p["mlp"], h, cfg.mlp_type, axis=ax) if sp_ok
             else mlp_apply(p["mlp"], h, cfg.mlp_type))
        aux = jnp.float32(0.0)
    return constrain_seq(x + y), aux


def _mamba_block_forward(cfg: ModelConfig, p: PyTree, x):
    x = constrain_seq(x)
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    return constrain_seq(x + ssm_mod.ssm_forward(cfg, p["ssm"], h))


# ---------------------------------------------------------------------------
# Full-sequence stacks (training)
# ---------------------------------------------------------------------------

def stack_forward(cfg: ModelConfig, params: PyTree, x: jnp.ndarray,
                  positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.family == "ssm":
        def body(carry, layer_p):
            return _mamba_block_forward(cfg, layer_p, carry), None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return x, jnp.float32(0.0)

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = max(cfg.n_layers // every, 1)
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]),
            params["layers"])
        shared = params["shared"]

        def group_body(carry, group_p):
            def inner(c, lp):
                return _mamba_block_forward(cfg, lp, c), None
            h, _ = jax.lax.scan(inner, carry, group_p)
            h, _ = _tf_block_forward(cfg, shared, h, positions, is_moe=False)
            return h, None

        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        x, _ = jax.lax.scan(group_body, x, grouped)
        return x, jnp.float32(0.0)

    # transformer families (dense / moe / audio / vlm)
    aux0 = jnp.float32(0.0)
    for dp in params.get("dense_layers", []):
        x, _ = _tf_block_forward(cfg, dp, x, positions, is_moe=False)

    is_moe = _layer_is_moe(cfg)

    def body(carry, layer_p):
        h, aux = carry
        h, a = _tf_block_forward(cfg, layer_p, h, positions, is_moe)
        return (h, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    return x, aux


# ---------------------------------------------------------------------------
# Prefill stacks: forward + cache construction
# ---------------------------------------------------------------------------

def _tf_block_prefill(cfg: ModelConfig, p: PyTree, x, positions, is_moe):
    """Returns (x, cache_entry) where cache_entry holds this layer's
    full-sequence KV (scatter to ring at model level)."""
    x = constrain_seq(x)
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    if cfg.mla:
        c_kv, k_rope = mla_mod._latents(cfg, p["mla"], h, positions)
        a = mla_mod.mla_full(cfg, p["mla"], h, positions)
        entry = {"ckv": c_kv, "krope": k_rope}
    else:
        q, k, v = attn._project_qkv(cfg, p["attn"], h, positions)
        mask = attn._causal_mask(h.shape[1], cfg.sliding_window, jnp.float32)
        out = attn._sdpa(q, k, v, mask, cfg)
        a = out @ p["attn"]["o"]["w"]
        entry = {"k": k, "v": v}
    x = x + a
    h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if is_moe:
        y, _ = moe_mod.moe_apply(cfg, p["moe"], h)
    else:
        y = mlp_apply(p["mlp"], h, cfg.mlp_type)
    return x + y, entry


def _mamba_block_prefill(cfg: ModelConfig, p: PyTree, x):
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    y, state, conv_tail = ssm_mod.ssm_prefill(cfg, p["ssm"], h)
    return x + y, {"state": state, "conv": conv_tail}


def stack_prefill(cfg: ModelConfig, params: PyTree, x, positions):
    """Returns (x, caches) with cache leaves stacked over scanned layers.
    For heterogeneous extras (dense layers / shared block) cache entries are
    returned under separate keys."""
    caches: PyTree = {}
    if cfg.family == "ssm":
        def body(carry, lp):
            h, entry = _mamba_block_prefill(cfg, lp, carry)
            return h, entry
        x, entries = jax.lax.scan(body, x, params["layers"])
        caches["layers"] = entries
        return x, caches

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = max(cfg.n_layers // every, 1)
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]),
            params["layers"])
        shared = params["shared"]

        def group_body(carry, gp):
            def inner(c, lp):
                return _mamba_block_prefill(cfg, lp, c)
            h, m_entries = jax.lax.scan(inner, carry, gp)
            h, s_entry = _tf_block_prefill(cfg, shared, h, positions,
                                           is_moe=False)
            return h, (m_entries, s_entry)

        x, (m_entries, s_entries) = jax.lax.scan(group_body, x, grouped)
        # m_entries leaves: (n_groups, every, ...) -> flatten to (L, ...)
        caches["layers"] = jax.tree.map(
            lambda a: a.reshape((n_groups * every,) + a.shape[2:]), m_entries)
        caches["shared"] = s_entries          # (n_groups, ...)
        return x, caches

    is_moe = _layer_is_moe(cfg)
    dense_entries = []
    for dp in params.get("dense_layers", []):
        x, e = _tf_block_prefill(cfg, dp, x, positions, is_moe=False)
        dense_entries.append(e)

    def body(carry, lp):
        h, e = _tf_block_prefill(cfg, lp, carry, positions, is_moe)
        return h, e

    x, entries = jax.lax.scan(body, x, params["layers"])
    caches["layers"] = entries
    if dense_entries:
        caches["dense_layers"] = dense_entries
    return x, caches


# ---------------------------------------------------------------------------
# Decode stacks: one token, threading cache slices
# ---------------------------------------------------------------------------

def _tf_block_decode(cfg: ModelConfig, p: PyTree, x, cache, pos, is_moe):
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    if cfg.mla:
        a, cache = mla_mod.mla_decode(cfg, p["mla"], h, cache, pos)
    else:
        a, cache = attn.attention_decode(cfg, p["attn"], h, cache, pos)
    x = x + a
    h = norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if is_moe:
        y, _ = moe_mod.moe_apply(cfg, p["moe"], h)
    else:
        y = mlp_apply(p["mlp"], h, cfg.mlp_type)
    return x + y, cache


def _mamba_block_decode(cfg: ModelConfig, p: PyTree, x, cache):
    h = norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    y, cache = ssm_mod.ssm_decode(cfg, p["ssm"], h, cache)
    return x + y, cache


def stack_decode(cfg: ModelConfig, params: PyTree, caches: PyTree,
                 x: jnp.ndarray, pos: jnp.ndarray):
    """x (B,1,D); caches as produced by model.init_cache/prefill."""
    if cfg.family == "ssm":
        def body(carry, xs):
            lp, cache = xs
            h, cache = _mamba_block_decode(cfg, lp, carry, cache)
            return h, cache
        x, new = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        return x, {"layers": new}

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = max(cfg.n_layers // every, 1)
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]),
            params["layers"])
        grouped_cache = jax.tree.map(
            lambda a: a.reshape((n_groups, every) + a.shape[1:]),
            caches["layers"])
        shared = params["shared"]

        def group_body(carry, xs):
            gp, gc, sc = xs

            def inner(c, ys):
                lp, lc = ys
                h, lc = _mamba_block_decode(cfg, lp, c, lc)
                return h, lc

            h, gc = jax.lax.scan(inner, carry, (gp, gc))
            h, sc = _tf_block_decode(cfg, shared, h, sc, pos, is_moe=False)
            return h, (gc, sc)

        x, (new_m, new_s) = jax.lax.scan(
            group_body, x, (grouped, grouped_cache, caches["shared"]))
        return x, {
            "layers": jax.tree.map(
                lambda a: a.reshape((n_groups * every,) + a.shape[2:]), new_m),
            "shared": new_s,
        }

    is_moe = _layer_is_moe(cfg)
    new_caches: PyTree = {}
    if "dense_layers" in caches:
        new_dense = []
        for dp, dc in zip(params["dense_layers"], caches["dense_layers"]):
            x, dc = _tf_block_decode(cfg, dp, x, dc, pos, is_moe=False)
            new_dense.append(dc)
        new_caches["dense_layers"] = new_dense

    def body(carry, xs):
        lp, lc = xs
        h, lc = _tf_block_decode(cfg, lp, carry, lc, pos, is_moe)
        return h, lc

    x, new = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
    new_caches["layers"] = new
    return x, new_caches
