"""The paper's own model (Sec. 6.1.3): the McMahan et al. CNN.

Two 5x5 conv layers (32 then 64 channels), each followed by 2x2 max pooling,
then a 512-unit dense layer and a 10-way softmax head (~1.66M parameters).
Pure JAX (lax.conv + reduce_window); a small MLP and a multinomial logistic
regression head are included for the strongly-convex validation experiments
(Assumption 1 holds exactly for L2-regularized logistic regression).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = ["init_cnn", "cnn_apply", "init_mlp", "mlp_apply",
           "init_logreg", "logreg_apply", "softmax_xent", "accuracy",
           "l2_regularized_loss"]


def _he(rng, shape, fan_in):
    return (rng.standard_normal(shape) * np.sqrt(2.0 / fan_in)).astype(
        np.float32)


def init_cnn(seed: int = 0, n_classes: int = 10,
             image_hw: int = 28, channels: int = 1) -> PyTree:
    rng = np.random.default_rng(seed)
    hw4 = image_hw // 4
    return {
        "conv1": {"w": jnp.asarray(_he(rng, (5, 5, channels, 32), 25 * channels)),
                  "b": jnp.zeros(32)},
        "conv2": {"w": jnp.asarray(_he(rng, (5, 5, 32, 64), 25 * 32)),
                  "b": jnp.zeros(64)},
        "fc1": {"w": jnp.asarray(_he(rng, (hw4 * hw4 * 64, 512), hw4 * hw4 * 64)),
                "b": jnp.zeros(512)},
        "fc2": {"w": jnp.asarray(_he(rng, (512, n_classes), 512)),
                "b": jnp.zeros(n_classes)},
    }


def _conv(x, w, b):
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out + b


def _max_pool_2x2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, window_dimensions=(1, 2, 2, 1),
        window_strides=(1, 2, 2, 1), padding="VALID")


def cnn_apply(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    """x: (B, H, W, C) -> logits (B, n_classes)."""
    h = _max_pool_2x2(jax.nn.relu(_conv(x, params["conv1"]["w"],
                                        params["conv1"]["b"])))
    h = _max_pool_2x2(jax.nn.relu(_conv(h, params["conv2"]["w"],
                                        params["conv2"]["b"])))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def init_mlp(seed: int = 0, d_in: int = 784, d_hidden: int = 64,
             n_classes: int = 10) -> PyTree:
    rng = np.random.default_rng(seed)
    return {
        "fc1": {"w": jnp.asarray(_he(rng, (d_in, d_hidden), d_in)),
                "b": jnp.zeros(d_hidden)},
        "fc2": {"w": jnp.asarray(_he(rng, (d_hidden, n_classes), d_hidden)),
                "b": jnp.zeros(n_classes)},
    }


def mlp_apply(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    h = x.reshape(x.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def init_logreg(seed: int = 0, d_in: int = 784, n_classes: int = 10) -> PyTree:
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(_he(rng, (d_in, n_classes), d_in) * 0.1),
            "b": jnp.zeros(n_classes)}


def logreg_apply(params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0], -1) @ params["w"] + params["b"]


# ---------------------------------------------------------------------------
# Losses / metrics
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


@functools.partial(jax.jit, static_argnums=0)
def l2_regularized_loss(apply_fn, params: PyTree, batch, mu: float = 1e-2
                        ) -> jnp.ndarray:
    """mu-strongly-convex loss (cross-entropy + (mu/2)||params||^2) --
    satisfies Assumption 1 exactly for the logistic-regression head.

    One compiled program per call, keyed on ``apply_fn`` (static, so a
    caller should build it once, not a new closure per call) and the
    shapes and dtypes of ``params`` and ``batch``; ``mu`` is traced.
    Inside another trace (the round program's ``grad``/``vmap``) the call
    is inlined.  ``l2_regularized_loss.__wrapped__`` is the plain
    function."""
    x, y = batch
    ce = softmax_xent(apply_fn(params, x), y)
    sq = sum(jnp.sum(jnp.square(p)) for p in jax.tree.leaves(params))
    return ce + 0.5 * mu * sq


@functools.partial(jax.jit, static_argnums=(0, 4))
def _accuracy_hits(apply_fn, params: PyTree, x: jnp.ndarray, y: jnp.ndarray,
                   batch: int) -> jnp.ndarray:
    """The int32 count of correct argmax predictions, ``batch`` samples
    at a time: ``lax.map`` over the full batches, then the remainder."""
    def count(xb, yb):
        return jnp.sum(jnp.argmax(apply_fn(params, xb), -1) == yb,
                       dtype=jnp.int32)

    k = len(y) // batch
    hits = jnp.int32(0)
    if k:
        cut = k * batch
        xk = x[:cut].reshape(k, batch, *x.shape[1:])
        yk = y[:cut].reshape(k, batch)
        hits += jax.lax.map(lambda b: count(*b), (xk, yk)).sum()
    if len(y) > k * batch:
        hits += count(x[k * batch:], y[k * batch:])
    return hits


def accuracy(apply_fn, params: PyTree, x: jnp.ndarray, y: jnp.ndarray,
             batch: int = 512) -> float:
    """Share of ``y`` that ``apply_fn``'s argmax predicts, with the logits
    of at most ``batch`` samples alive at a time.

    One compiled program and one host transfer per call, keyed on
    ``apply_fn`` and ``batch`` (both static: build ``apply_fn`` once, not
    a new closure per call) and the shapes and dtypes of ``params``,
    ``x`` and ``y``."""
    return int(_accuracy_hits(apply_fn, params, x, y, batch)) / len(y)
