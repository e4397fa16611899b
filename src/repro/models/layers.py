"""Shared neural building blocks: norms, RoPE, MLPs, initializers."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = ["rms_norm", "layer_norm", "norm", "norm_init", "rope_angles",
           "apply_rope", "yarn_inv_freq", "yarn_mscale",
           "mlp_init", "mlp_apply", "dense_init", "he_normal", "lecun_normal"]


# ---------------------------------------------------------------------------
# Initializers (explicit key-based; used by model.init)
# ---------------------------------------------------------------------------

def lecun_normal(key, shape, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 1 else 1
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def he_normal(key, shape, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) >= 1 else 1
    return (jax.random.normal(key, shape) * jnp.sqrt(2.0 / fan_in)).astype(dtype)


def dense_init(key, d_in, d_out, dtype=jnp.float32, bias=False):
    p = {"w": lecun_normal(key, (d_in, d_out), dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)
            ).astype(dt)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-6):
    """``torch.nn.LayerNorm``: a learned scale and shift (bias)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (((x32 - mean) * jax.lax.rsqrt(var + eps))
            * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(dt)


def norm_init(d: int, kind: str = "rms", dtype=jnp.float32) -> PyTree:
    """A norm's params: the scale alone for RMS norm, ``{"scale",
    "bias"}`` (ones and zeros) for layer norm."""
    if kind == "rms":
        return jnp.ones((d,), dtype)
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def norm(x, p, kind: str = "rms", eps: float = 1e-6):
    """``p`` as ``norm_init`` makes it for ``kind``."""
    if kind == "rms":
        return rms_norm(x, p, eps)
    return layer_norm(x, p["scale"], p["bias"], eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_angles(positions: jnp.ndarray, dim: int, theta: float = 10_000.0,
                inv_freq: Optional[jnp.ndarray] = None):
    """positions (...,) -> (cos, sin) of shape (..., dim//2); ``inv_freq``
    (dim//2,) replaces the plain frequencies (``yarn_inv_freq``)."""
    half = dim // 2
    freqs = (1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 at
    ``factor <= 1``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """YaRN frequencies of ``dim`` rotary dims (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): dims whose wavelength fits
    ``original`` positions ``beta_fast`` times or more keep the plain
    frequency, those below ``beta_slow`` times take it over ``factor``,
    and a linear ramp blends the ones between."""
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) * 2 / dim)
    inter = extra / factor

    def corr(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(inter * ramp + extra * (1 - ramp), jnp.float32)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               fraction: float = 1.0) -> jnp.ndarray:
    """Rotate the first ``fraction`` of the head dim.

    x: (..., seq, heads, head_dim); cos/sin: (seq, rot_dim//2) broadcast.
    Pairs are (x[..., :half], x[..., half:rot]) -- the "rotate_half" layout
    used by the LLaMA/Qwen family.
    """
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    x1, x2 = xr[..., :half], xr[..., half:]
    # cos/sin: (seq, half) -> broadcast over heads axis
    c = cos[..., None, :].astype(x.dtype)
    s = sin[..., None, :].astype(x.dtype)
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2, xp], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32) -> PyTree:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"gate": lecun_normal(ks[0], (d_model, d_ff), dtype),
                "up": lecun_normal(ks[1], (d_model, d_ff), dtype),
                "down": lecun_normal(ks[2], (d_ff, d_model), dtype)}
    return {"up": lecun_normal(ks[0], (d_model, d_ff), dtype),
            "down": lecun_normal(ks[1], (d_ff, d_model), dtype)}


def mlp_apply_sp(p: PyTree, x: jnp.ndarray, kind: str = "swiglu",
                 axis: str = "model") -> jnp.ndarray:
    """Sequence-parallel MLP via explicit shard_map (§Perf, beyond-GSPMD).

    Contract: ``x`` (B, S, D) arrives sequence-sharded over ``axis``; the
    ffn weights are ffn-dim-sharded.  Per shard: all-gather the sequence,
    run the local ffn slice, reduce-scatter the partial outputs back to the
    seq-sharded layout -- the Megatron-SP schedule that GSPMD does not
    synthesize from sharding constraints alone (it keeps the all-reduce and
    adds resharding; see EXPERIMENTS §Perf pair A).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if kind != "swiglu":
        raise ValueError("sp mlp implemented for swiglu")

    def body(gate, up, down, xs):
        xfull = jax.lax.all_gather(xs, axis, axis=1, tiled=True)
        h = jax.nn.silu(xfull @ gate) * (xfull @ up)
        y = (h @ down).astype(xs.dtype)
        return jax.lax.psum_scatter(y, axis, scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(
        body,
        in_specs=(P(None, axis), P(None, axis), P(axis, None),
                  P(None, axis, None)),
        out_specs=P(None, axis, None), check_vma=False,
        # manual over the model axis ONLY -- composes under the partial
        # client shard_map (client_impl='shardmap'), where claiming the
        # other axes would assert per-client activations are replicated
        axis_names={axis},
    )(p["gate"], p["up"], p["down"], x)


def mlp_apply(p: PyTree, x: jnp.ndarray, kind: str = "swiglu") -> jnp.ndarray:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    else:
        h = jax.nn.gelu(x @ p["up"])
    return h @ p["down"]
