"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Keys/values are generated from a low-rank latent ``c_kv`` (kv_lora_rank) plus
a small shared rotary key ``k_rope``.  Decode caches ONLY ``(c_kv, k_rope)``
-- (512 + 64) floats per token instead of 2*H*hd -- and uses the standard
weight-absorption trick: ``q_nope`` is mapped through ``W_UK`` into latent
space so attention scores/values are computed directly against the latent
cache.

Sharding note (DESIGN §5): the latent cache is head-agnostic, so it is
replicated over the ``model`` axis and sharded over batch; the per-head
up-projections ``W_UK``/``W_UV`` shard over heads.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import NEG_INF
from .config import ModelConfig
from .layers import (lecun_normal, rms_norm, rope_angles, yarn_inv_freq,
                     yarn_mscale)

PyTree = Any

__all__ = ["mla_init", "mla_full", "mla_decode", "make_mla_cache"]


def mla_init(key, cfg: ModelConfig, dtype) -> PyTree:
    d, H = cfg.d_model, cfg.n_heads
    r, rq = cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": lecun_normal(ks[0], (d, r + dr), dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": lecun_normal(ks[1], (r, H * (dn + dv)), dtype),
        "wo": lecun_normal(ks[2], (H * dv, d), dtype),
    }
    if rq:
        p["wq_a"] = lecun_normal(ks[3], (d, rq), dtype)
        p["q_norm"] = jnp.ones((rq,), dtype)
        p["wq_b"] = lecun_normal(ks[4], (rq, H * (dn + dr)), dtype)
    else:
        p["wq"] = lecun_normal(ks[5], (d, H * (dn + dr)), dtype)
    return p


def _rope(cfg: ModelConfig, positions):
    """cos, sin of the rope dims: YaRN frequencies where the config scales
    rope (its cos/sin factor ``mscale(f, mscale) / mscale(f,
    mscale_all_dim)`` is 1 whenever the two are equal, as published)."""
    dr = cfg.rope_head_dim
    inv_freq = None
    if cfg.yarn_factor:
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, cfg.yarn_factor,
                                 cfg.yarn_original_max_pos,
                                 cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    cos, sin = rope_angles(positions, dr, cfg.rope_theta, inv_freq)
    if cfg.yarn_factor:
        m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def softmax_scale(cfg: ModelConfig) -> float:
    """``(dn + dr)^-0.5``, times YaRN's ``mscale(f, mscale_all_dim)^2``
    where the config scales rope."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _queries(cfg: ModelConfig, p: PyTree, x, positions):
    """-> q_nope (B,S,H,dn), q_rope (B,S,H,dr) (roped)."""
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = _rope(cfg, positions)
    c = cos[..., None, :].astype(q_rope.dtype)
    s = sin[..., None, :].astype(q_rope.dtype)
    half = dr // 2
    q1, q2 = q_rope[..., :half], q_rope[..., half:]
    q_rope = jnp.concatenate([q1 * c - q2 * s, q2 * c + q1 * s], axis=-1)
    return q_nope, q_rope


def _latents(cfg: ModelConfig, p: PyTree, x, positions):
    """-> c_kv (B,S,r) [normed], k_rope (B,S,dr) (roped, head-shared)."""
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., r:]
    cos, sin = _rope(cfg, positions)
    half = dr // 2
    k1, k2 = k_rope[..., :half], k_rope[..., half:]
    c = cos.astype(k_rope.dtype)
    s = sin.astype(k_rope.dtype)
    k_rope = jnp.concatenate([k1 * c - k2 * s, k2 * c + k1 * s], axis=-1)
    return c_kv, k_rope


def mla_full(cfg: ModelConfig, p: PyTree, x: jnp.ndarray,
             positions: jnp.ndarray,
             window: Optional[int] = "cfg") -> jnp.ndarray:
    """Full-sequence causal MLA (training / prefill): materializes per-head
    K/V from the latent (the flop-efficient choice when S == #queries).
    Runs under ``jax.named_scope("mla")``."""
    with jax.named_scope("mla"):
        return _mla_full(cfg, p, x, positions, window)


def _mla_full(cfg, p, x, positions, window):
    if window == "cfg":
        window = cfg.sliding_window
    B, S, _ = x.shape
    H, dn, dr, dv = (cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim,
                     cfg.v_head_dim)
    q_nope, q_rope = _queries(cfg, p, x, positions)
    c_kv, k_rope = _latents(cfg, p, x, positions)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]

    scale = softmax_scale(cfg)
    C = min(cfg.attn_chunk, S) if cfg.attn_impl == "chunked" else S
    if cfg.attn_impl == "chunked" and C < S:
        # query-chunked: scores stay at (B, H, C, S), never (B, H, S, S);
        # queries padded to a chunk multiple (padded rows sliced away).
        nC = -(-S // C)
        Sp = nC * C
        if Sp != S:
            q_nope = jnp.pad(q_nope, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
            q_rope = jnp.pad(q_rope, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        qn = jnp.moveaxis(q_nope.reshape(B, nC, C, H, dn), 1, 0)
        qr = jnp.moveaxis(q_rope.reshape(B, nC, C, H, dr), 1, 0)
        j = jnp.arange(S)[None, :]

        def chunk(carry, xs):
            qnc, qrc, i0 = xs
            i = i0 + jnp.arange(C)[:, None]
            ok = j <= i
            if window is not None:
                ok &= (i - j) < window
            ok |= i >= S                       # padded rows: keep finite
            s = (jnp.einsum("bshd,bthd->bhst", qnc, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bshd,btd->bhst", qrc, k_rope,
                              preferred_element_type=jnp.float32)) * scale
            s = s + jnp.where(ok, 0.0, NEG_INF)[None, None]
            w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            return carry, jnp.einsum("bhst,bthd->bshd", w, v)

        _, outs = jax.lax.scan(chunk, None,
                               (qn, qr, jnp.arange(nC) * C))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, Sp, H * dv)[:, :S]
        return out @ p["wo"]

    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    ok = j <= i
    if window is not None:
        ok &= (i - j) < window
    scores = scores + jnp.where(ok, 0.0, NEG_INF)[None, None]
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(B, S, H * dv)
    return out @ p["wo"]


def make_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   n_layers: int, dtype) -> PyTree:
    return {
        "ckv": jnp.zeros((n_layers, batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((n_layers, batch, max_len, cfg.rope_head_dim),
                           dtype),
        "kpos": jnp.full((n_layers, max_len), -1, jnp.int32),
    }


def mla_decode(cfg: ModelConfig, p: PyTree, x: jnp.ndarray, cache: PyTree,
               pos: jnp.ndarray, window: Optional[int] = "cfg"
               ) -> Tuple[jnp.ndarray, PyTree]:
    """One decode step with the absorbed latent cache.

    x (B,1,D); cache leaves per-layer: ckv (B,W,r), krope (B,W,dr),
    kpos (W,).  O(W * (r + dr)) work per head-free score pass.
    """
    if window == "cfg":
        window = cfg.sliding_window
    B = x.shape[0]
    H, dn, dr, dv = (cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim,
                     cfg.v_head_dim)
    r = cfg.kv_lora_rank

    q_nope, q_rope = _queries(cfg, p, x, positions=pos[None])
    c_kv, k_rope = _latents(cfg, p, x, positions=pos[None])

    W = cache["ckv"].shape[1]
    slot = (pos % W).astype(jnp.int32)
    ckv = jax.lax.dynamic_update_slice(cache["ckv"], c_kv, (0, slot, 0))
    krope = jax.lax.dynamic_update_slice(cache["krope"], k_rope, (0, slot, 0))
    kpos = jax.lax.dynamic_update_slice(cache["kpos"], pos[None], (slot,))

    # weight absorption: W_UK (r, H, dn) pulled out of wkv_b
    wkv_b = p["wkv_b"].reshape(r, H, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)   # (B,1,H,r)

    age = pos - kpos
    ok = (kpos >= 0) & (age >= 0)
    if window is not None:
        ok &= age < window
    mask = jnp.where(ok, 0.0, NEG_INF)

    scale = softmax_scale(cfg)
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshd,btd->bhst", q_rope, krope,
                           preferred_element_type=jnp.float32)) * scale
    scores = scores + mask[None, None, None]
    wts = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", wts, ckv)      # (B,1,H,r)
    out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv)     # (B,1,H,dv)
    y = out.reshape(B, 1, H * dv) @ p["wo"]
    return y, {"ckv": ckv, "krope": krope, "kpos": kpos}
