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

from typing import List, Tuple

import jax.numpy as jnp
import numpy as np

from repro.spans import span

from .synthetic import Dataset

__all__ = ["FederatedBatcher", "lm_batches"]


class FederatedBatcher:
    def __init__(self, ds: Dataset, parts: List[np.ndarray], T: int,
                 batch_size: int):
        self.ds = ds
        self.parts = parts
        self.T = T
        self.batch_size = batch_size

    @property
    def n_clients(self) -> int:
        return len(self.parts)

    @span("data.batch")
    def __call__(self, rng: np.random.Generator, t: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x, y) with shapes (n, T, B, ...) / (n, T, B)."""
        n, T, B = self.n_clients, self.T, self.batch_size
        xs = np.empty((n, T, B) + self.ds.x.shape[1:], dtype=self.ds.x.dtype)
        ys = np.empty((n, T, B), dtype=self.ds.y.dtype)
        for i, part in enumerate(self.parts):
            idx = rng.choice(part, size=(T, B), replace=True)
            xs[i] = self.ds.x[idx]
            ys[i] = self.ds.y[idx]
        return jnp.asarray(xs), jnp.asarray(ys)


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
