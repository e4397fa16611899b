"""Federated batch sampling: minibatches per client per local-SGD step.

The server's round function expects pytrees with leading axes
``(n_clients, T, batch, ...)`` -- T independent minibatches per client per
global round (one per local SGD iteration, eq. 1).  ``FederatedBatcher``
draws them from the per-client index partitions with replacement across
rounds (standard SGD sampling).

Also provides ``lm_batches`` for token-stream training of the transformer
stack.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.spans import span

from .synthetic import Dataset

__all__ = ["FederatedBatcher", "lm_batches"]

# A TPU tile's minor dimension.  Rows of a whole number of lanes keep the
# row-major layout; (N, 784) fp32 is laid out sample-minor instead, and a
# row gather from it relays out the whole training set on every call.
LANES = 128


def _lane_rows(x: np.ndarray) -> np.ndarray:
    """``x`` as (N, D') rows, each sample's features flattened and
    zero-padded to a whole number of ``LANES``."""
    rows = x.reshape(len(x), -1)
    pad = -rows.shape[1] % LANES
    return np.pad(rows, ((0, 0), (0, pad))) if pad else rows


@partial(jax.jit, static_argnames=("shape",))
def _gather(x_rows: jax.Array, y: jax.Array, idx: jax.Array,
            shape: Tuple[int, ...]) -> Tuple[jax.Array, jax.Array]:
    """Samples ``idx`` of the device-held rows: ``idx.shape + shape`` and
    ``idx.shape``."""
    x = x_rows[idx][..., :math.prod(shape)]
    return x.reshape(idx.shape + shape), y[idx]


class FederatedBatcher:
    """Each call draws the round's sample indices on the host from the
    client partitions and gathers the batch on the device.

    The batcher holds the whole training set on the default device,
    uploaded once on the first call (``ds.x`` as ``_lane_rows``, 215 MB
    for 60,000 MNIST-shaped fp32 samples); after that a call sends only
    the (n, T, B) int32 indices.  The draws are the ``rng`` calls of a
    host gather, in the same order, so the batch stream is bitwise the
    same for a given generator."""

    def __init__(self, ds: Dataset, parts: List[np.ndarray], T: int,
                 batch_size: int):
        for part in parts:
            if len(part) and not 0 <= np.min(part) <= np.max(part) < len(ds):
                raise ValueError(
                    f"partition indices outside [0, {len(ds)})")
        self.ds = ds
        self.parts = parts
        self.T = T
        self.batch_size = batch_size
        self._device = None       # (x rows, y) on the device, once uploaded

    @property
    def n_clients(self) -> int:
        return len(self.parts)

    def _on_device(self) -> Tuple[jax.Array, jax.Array]:
        if self._device is None:
            with span("data.upload"):
                self._device = (jax.device_put(_lane_rows(self.ds.x)),
                                jax.device_put(self.ds.y))
        return self._device

    @span("data.batch")
    def __call__(self, rng: np.random.Generator, t: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x, y) with shapes (n, T, B, ...) / (n, T, B)."""
        x_rows, y = self._on_device()
        idx = np.empty((self.n_clients, self.T, self.batch_size), np.int32)
        for i, part in enumerate(self.parts):
            idx[i] = rng.choice(part, size=(self.T, self.batch_size),
                                replace=True)
        with span("data.gather"):
            return _gather(x_rows, y, jax.device_put(idx),
                           shape=self.ds.x.shape[1:])


def lm_batches(tokens: np.ndarray, rng: np.random.Generator, n_clients: int,
               T: int, batch_size: int, seq_len: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(inputs, targets) of shape (n, T, B, seq_len) from a token stream.

    Clients get disjoint contiguous stream regions (non-iid in n-gram
    statistics since the stream's transition table is position-independent
    but region sampling keeps client batches decorrelated)."""
    n_tok = len(tokens)
    region = n_tok // n_clients
    starts_max = region - seq_len - 1
    if starts_max <= 0:
        raise ValueError("token stream too short for this seq_len")
    xs = np.empty((n_clients, T, batch_size, seq_len), dtype=np.int32)
    ys = np.empty_like(xs)
    for i in range(n_clients):
        base = i * region
        starts = base + rng.integers(0, starts_max, size=(T, batch_size))
        for t in range(T):
            for b in range(batch_size):
                s = starts[t, b]
                xs[i, t, b] = tokens[s:s + seq_len]
                ys[i, t, b] = tokens[s + 1:s + seq_len + 1]
    return jnp.asarray(xs), jnp.asarray(ys)
