"""Pallas TPU kernel for the D2D graph-mixing operator ``Delta = A @ X``.

This is the compute hot-spot the paper's technique adds to every global
round: an (n x n) mixing matmul whose payload ``X`` is the concatenation of
every client's flattened model delta -- p is the model dimension (millions
to billions), n the client count (tens).  The op is memory-bound
(arithmetic intensity ~= n flops/byte), so the kernel is designed around
streaming ``X`` through VMEM exactly once:

* grid over payload chunks (the p axis); each step loads an (n, pc) tile of
  ``X`` plus the whole (n, n) matrix ``A`` (tiny -- kilobytes) into VMEM,
  issues one MXU matmul, and writes the (n, pc) output tile.
* ``pc`` is a multiple of 128 (lane width) and the client axis is padded to
  the float32 sublane multiple (8) by the wrapper in ``ops.py``.
* accumulation in float32 regardless of payload dtype (bf16 deltas are
  upcast on the MXU, matching the reference oracle).

Validated in interpret mode on CPU against ``ref.mix_ref`` (see
tests/test_kernels.py); TPU is the target for the compiled path.

``fused.py`` extends this design to a one-pass mix *plus* D2S aggregate
(eq. 3 + eq. 4 from a single streaming read of ``X``) -- prefer it on the
round hot path (``make_round_fn(..., mixing_backend='fused')``); this
mix-only kernel remains for the 'pallas' leaf-wise backend and ablations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["mix_pallas", "f32_matmul"]


def f32_matmul(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """``a @ x`` of fp32 operands with fp32 products and accumulation.
    Mosaic's default precision rounds fp32 operands to bf16 (on a v5e
    that put ~2e-3 relative error into the CNN aggregate); HIGHEST keeps
    the kernels at the fp32 reference's accuracy."""
    return jax.lax.dot_general(
        a, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def _mix_kernel(a_ref, x_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)          # (n_pad, n_pad)
    x = x_ref[...].astype(jnp.float32)          # (n_pad, pc)
    o_ref[...] = f32_matmul(a, x).astype(o_ref.dtype)


def mix_pallas(A: jnp.ndarray, X: jnp.ndarray, *, chunk: int = 2048,
               interpret: bool = True) -> jnp.ndarray:
    """A (n_pad, n_pad), X (n_pad, p_pad) with p_pad % chunk == 0.

    Padding/unpadding is the wrapper's job (ops.py); this function assumes
    hardware-aligned shapes.
    """
    n, p = X.shape
    assert A.shape == (n, n), (A.shape, X.shape)
    assert p % chunk == 0, (p, chunk)
    grid = (p // chunk,)
    return pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((n, chunk), lambda i: (0, i)),    # stream X
        ],
        out_specs=pl.BlockSpec((n, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, p), X.dtype),
        interpret=interpret,
        name="mix",
    )(A, X)
