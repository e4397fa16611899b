"""Plain reference for the FL round on StableLM-2-1.6B.

Written from the paper (arXiv:2303.08988, Alg. 1 and eqs. 1-4) and the
published StableLM-2-1.6B (huggingface.co/stabilityai/stablelm-2-1_6b,
``config.json`` and its modelling code), in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes only the inputs: the initial weights, the clients'
token windows, and the plan's columns (A, tau, active, eta).

The decoder, per layer (pre-norm, sequential residual):
    h = LayerNorm(x)                  (learned scale and shift, eps)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv        (biases on q, k, v)
    rotary on the first 25% of each 64-dim head (rotate-half, theta)
    x = x + softmax(q k^T / sqrt(64) + causal) v Wo
    x = x + (silu(LayerNorm(x) Wgate) * (LayerNorm(x) Wup)) Wdown
then a final LayerNorm and the untied head; the loss is the mean
next-token cross-entropy.

One round, for every client i (one per device where there are enough):
    T SGD steps on its windows from the global x:   x_i = x - eta sum g
    its delta d_i = x_i - x (zero for a dropped client);
then the D2D mix D = A d (eq. 3), and the server update
    x <- x + (1 / m) sum_i tau_i active_i D_i          (eq. 4)
with m the number of sampled clients that are active.

Departures from the published model: the weights are the program's (a
seeded draw, not the checkpoint); each layer is recomputed in the
backward pass (``jax.checkpoint``), which changes no value; eqs. 3 and 4
are one contraction over both client indices, so the mixed deltas are
never held at once.

``dtype`` and ``fault`` give the control and the faults that a cell's
limits must catch: ``dtype="bfloat16"`` runs the whole reference one
precision lower; ``fault`` is one of ``FAULTS``.  ``half_batch`` trains
each local step on the first half of every window (the same program,
with the loss over fewer positions).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FAULTS = ("frozen", "half_batch", "no_mixing")
CLIENTS = "clients"


class Arch(NamedTuple):
    """What the layer equations read of the published config."""
    heads: int
    rotary_dims: int
    eps: float
    theta: float

    @classmethod
    def of(cls, model: Dict[str, Any]) -> "Arch":
        hd = model["hidden_size"] // model["num_attention_heads"]
        return cls(model["num_attention_heads"],
                   int(hd * model["partial_rotary_factor"]),
                   float(model["layer_norm_eps"]), float(model["rope_theta"]))


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rotary(x, rot: int, theta: float):
    """Rotate-half rotary embedding of the first ``rot`` dims of each head;
    x (B, S, H, hd)."""
    half = rot // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                               / rot)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def layer(arch: Arch, x, p):
    B, S, d = x.shape
    H = arch.heads
    hd = d // H

    h = layer_norm(x, p["ln1"], arch.eps)

    def heads(name):
        y = h @ p["attn"][name]["w"] + p["attn"][name]["b"]
        return y.reshape(B, S, H, hd)

    q = rotary(heads("q"), arch.rotary_dims, arch.theta)
    k = rotary(heads("k"), arch.rotary_dims, arch.theta)
    v = heads("v")
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(
        jnp.asarray(hd, x.dtype))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, jnp.asarray(-jnp.inf, x.dtype))
    att = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), v)
    x = x + att.reshape(B, S, d) @ p["attn"]["o"]["w"]

    h = layer_norm(x, p["ln2"], arch.eps)
    mlp = p["mlp"]
    return x + (jax.nn.silu(h @ mlp["gate"]) * (h @ mlp["up"])) @ mlp["down"]


def logits(arch: Arch, p, tokens):
    x = p["embed"][tokens]

    def body(x, lp):
        return jax.checkpoint(partial(layer, arch))(x, lp), None

    x, _ = jax.lax.scan(body, x, p["decoder"]["layers"])
    x = layer_norm(x, p["final_norm"], arch.eps)
    return x @ p["lm_head"]


def loss(arch: Arch, p, window, used=None):
    """Mean next-token cross-entropy of (B, S+1) token windows, over the
    first ``used`` positions of each (all by default)."""
    inputs, targets = window[:, :-1], window[:, 1:]
    z = logits(arch, p, inputs)
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(z, axis=-1) - picked
    if used is None:
        return jnp.mean(nll)
    # causal: the first positions' outputs read only the first tokens
    keep = jnp.arange(nll.shape[-1]) < used
    return jnp.sum(jnp.where(keep, nll, 0)) / (jnp.sum(keep) * nll.shape[0])


def _round(arch, mesh, x, windows, used, A, tau, active, eta):
    """One round from x: windows (n, T, B, S+1), trained on their first
    ``used`` positions."""
    n = windows.shape[0]
    clients = NamedSharding(mesh, P(CLIENTS))

    def client(p, w, a):
        def step(p, window):
            g = jax.grad(partial(loss, arch, used=used))(p, window)
            return jax.tree.map(lambda q, gq: q - eta * gq, p, g), None

        final, _ = jax.lax.scan(step, p, w)
        return jax.tree.map(lambda f, s: (f - s) * a, final, p)

    xs = jax.lax.with_sharding_constraint(
        jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), x),
        clients)
    deltas = jax.vmap(client)(xs, windows, active)
    up = tau * active
    m = jnp.maximum(jnp.sum(up), 1)
    # eqs. 3 and 4: x + (1/m) sum_i up_i sum_j A_ij d_j
    return jax.tree.map(
        lambda g, d: g + jnp.einsum("i,ij,j...->...", up, A, d) / m,
        x, deltas)


_step = jax.jit(_round, static_argnums=(0, 1))
_test_loss = jax.jit(loss, static_argnums=0)


def _mesh(n: int) -> Mesh:
    devices = jax.devices()
    return Mesh(np.asarray(devices[:n] if len(devices) >= n
                           else devices[:1]), (CLIENTS,))


def run_rounds(model: Dict[str, Any], x0, batches: Sequence, rows: Sequence,
               test_set: np.ndarray, *, dtype: str = "float32",
               fault: Optional[str] = None) -> Tuple[List, List[float]]:
    """Run ``len(rows)`` rounds from ``x0`` (the program's params tree, host
    arrays) on the clients' token windows ``batches[t]`` (n, T, B, S+1);
    returns the params after each round (float32 host arrays) and the mean
    next-token loss on ``test_set`` (E, S+1) after each round."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    arch = Arch.of(model)
    dt = jnp.dtype(dtype)
    n = np.asarray(batches[0]).shape[0]
    mesh = _mesh(n)
    rep, clients = NamedSharding(mesh, P()), NamedSharding(mesh, P(CLIENTS))
    put = lambda a, s: jax.device_put(np.asarray(a), s)  # noqa: E731
    tests = put(test_set, clients if len(test_set) % mesh.size == 0
                else rep)
    x = jax.tree.map(lambda a: put(np.asarray(a, dt), rep), x0)
    params, losses = [], []
    with jax.default_matmul_precision("highest"):
        for window, row in zip(batches, rows):
            if fault != "frozen":
                A = np.eye(n) if fault == "no_mixing" else np.asarray(row.A)
                S = np.asarray(window).shape[-1] - 1
                used = S // 2 if fault == "half_batch" else S
                x = _step(arch, mesh, x, put(window, clients),
                          put(np.asarray(used, np.int32), rep),
                          put(A.astype(dt), rep),
                          put(np.asarray(row.tau, dt), rep),
                          put(np.asarray(row.active, dt), rep),
                          put(np.asarray(row.eta, dt), rep))
            params.append(jax.tree.map(
                lambda a: np.asarray(a, np.float32), x))
            losses.append(float(_test_loss(arch, x, tests)))
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
