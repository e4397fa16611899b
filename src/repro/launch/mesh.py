"""Production mesh construction (TPU v5e target).

Single pod: (16, 16)   -> axes ('data', 'model')   = 256 chips.
Multi-pod:  (2, 16, 16) -> axes ('pod', 'data', 'model') = 512 chips.

Semi-decentralized-FL mapping (DESIGN §2): a *client* is one (pod, data)
index; a *D2D cluster* is one pod (its ICI domain); the 'pod' axis carries
only the expensive cross-pod D2S collectives.  The 'model' axis carries
tensor parallelism inside every client.

These are FUNCTIONS (not module constants) so importing the module never
initializes jax device state -- required because smoke tests must see the
real 1-CPU backend while the dry-run forces 512 host devices.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

__all__ = ["make_production_mesh", "make_debug_mesh", "client_axes",
           "n_clients_of", "model_axis_size", "data_axis_size"]


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the train steps place
    tensors with ``with_sharding_constraint`` and ``NamedSharding``, which
    only accept Auto axes (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape: Tuple[int, ...],
                    axes: Optional[Tuple[str, ...]] = None):
    """Small-mesh variant for CPU tests (e.g. (2, 2, 2) on 8 host devices)."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):] if len(shape) == 3 \
            else ("data", "model")
    return _auto_mesh(shape, axes)


def client_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that enumerate FL clients (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_clients_of(mesh) -> int:
    n = 1
    for a in client_axes(mesh):
        n *= mesh.shape[a]
    return n


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def data_axis_size(mesh) -> int:
    """Size of the innermost client axis ('data') -- the reduce-scatter
    width of the worker-sharded fused mixing path, and the shard count the
    packed-delta buffer must split evenly across (``repro.fl.packing
    .pack_spec(..., shards=...)``)."""
    return mesh.shape[client_axes(mesh)[-1]]
