"""Plain reference for the FL round on the McMahan et al. CNN.

Written from the paper (arXiv:2303.08988, Alg. 1 and eqs. 1-4) and the
CNN of McMahan et al. (arXiv:1602.05629, two 5x5 convolutions of 32 and
64 channels, each with ReLU and 2x2 max pooling, a 512-unit ReLU layer,
a 10-way softmax), in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes only the inputs: the initial weights, the clients'
batches, and the plan's columns (A, tau, active, eta).

One round, for every client i (all at once):
    T SGD steps on its batches from the global x:   x_i = x - eta sum g
    its delta d_i = x_i - x (zero for a dropped client);
then the D2D mix D = A d (eq. 3), and the server update
    x <- x + (1 / m) sum_i tau_i active_i D_i          (eq. 4)
with m the number of sampled clients that are active.

``dtype`` and ``fault`` give the control and the faults that a cell's
limits must catch: ``dtype="bfloat16"`` runs the whole reference one
precision lower; ``fault`` is one of ``FAULTS``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("frozen", "half_batch", "no_mixing")


def forward(p, x):
    def conv(h, w, b):
        return jax.lax.conv_general_dilated(
            h, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(h):
        return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    h = pool(jnp.maximum(conv(x, p["conv1"]["w"], p["conv1"]["b"]), 0))
    h = pool(jnp.maximum(conv(h, p["conv2"]["w"], p["conv2"]["b"]), 0))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(h @ p["fc1"]["w"] + p["fc1"]["b"], 0)
    return h @ p["fc2"]["w"] + p["fc2"]["b"]


def loss(p, x, y, mu):
    """Mean cross-entropy plus (mu / 2) |p|^2."""
    logits = forward(p, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    sq = sum(jnp.sum(v * v) for v in jax.tree.leaves(p))
    return jnp.mean(logz - picked) + 0.5 * mu * sq


def _round(x, bx, by, A, tau, active, eta, mu, fault):
    def client(bxi, byi):
        def step(p, b):
            xb, yb = b
            if fault == "half_batch":
                half = xb.shape[0] // 2
                xb, yb = xb[:half], yb[:half]
            g = jax.grad(loss)(p, xb, yb, mu)
            return jax.tree.map(lambda a, gg: a - eta * gg, p, g), None

        final, _ = jax.lax.scan(step, x, (bxi, byi))
        return jax.tree.map(lambda f, g: f - g, final, x)

    deltas = jax.vmap(client)(bx, by)
    deltas = jax.tree.map(
        lambda d: d * active.reshape((-1,) + (1,) * (d.ndim - 1)), deltas)
    if fault == "no_mixing":
        A = jnp.eye(A.shape[0], dtype=A.dtype)
    up = tau * active
    m = jnp.maximum(jnp.sum(up), 1)

    def update(g, d):
        mixed = jnp.einsum("ij,j...->i...", A, d)
        return g + jnp.einsum("i,i...->...", up, mixed) / m

    new = jax.tree.map(update, x, deltas)
    if fault == "frozen":
        return x
    return new


_step = jax.jit(_round, static_argnames=("fault",))
_test_loss = jax.jit(loss)


def run_rounds(model: Dict[str, Any], x0, batches: Sequence, rows: Sequence,
               test_set: Tuple[np.ndarray, np.ndarray], *, eta: float,
               mu: float, dtype: str = "float32",
               fault: Optional[str] = None) -> Tuple[List, List[float]]:
    """Run ``len(rows)`` rounds from ``x0``; returns the params after each
    round (host arrays) and the test loss after each round."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    dt = jnp.dtype(dtype)
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dt), t)  # noqa
    tx, ty = jnp.asarray(test_set[0], dt), jnp.asarray(test_set[1])
    x = cast(x0)
    params, losses = [], []
    with jax.default_matmul_precision("highest"):
        for (bx, by), row in zip(batches, rows):
            x = _step(x, jnp.asarray(bx, dt), jnp.asarray(by),
                     jnp.asarray(row.A, dt), jnp.asarray(row.tau, dt),
                     jnp.asarray(row.active, dt), jnp.asarray(row.eta, dt),
                     jnp.asarray(mu, dt), fault=fault)
            params.append(jax.tree.map(
                lambda a: np.asarray(a, np.float32), x))
            losses.append(float(_test_loss(x, tx, ty, jnp.asarray(mu, dt))))
    return params, losses


def comm(A: np.ndarray, tau: np.ndarray, active: np.ndarray
         ) -> Tuple[int, int, int]:
    """(m, d2s, d2d) of one round, counted from its columns: every
    sampled active client uploads once; every active client sends to each
    of its out-neighbours (a nonzero off-diagonal entry of its column of
    A) once."""
    A = np.asarray(A)
    up = int(np.sum((np.asarray(tau) != 0) & (np.asarray(active) != 0)))
    off = (A != 0) & ~np.eye(A.shape[0], dtype=bool)
    d2d = int(np.sum(off[:, np.asarray(active) != 0]))
    return up, up, d2d
