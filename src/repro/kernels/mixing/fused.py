"""Fused one-pass Pallas kernel: D2D mix + D2S aggregate (paper eq. 3 + 4).

The per-round hot path is two back-to-back memory-bound passes over the
full client-delta payload ``X`` (n clients x p model dims):

    mixed = A @ X                       (eq. 3, D2D consensus)
    agg   = (1/m) sum_i tau_i mixed_i   (eq. 4, D2S aggregate)

At arithmetic intensity ~n flops/byte the HBM traffic *is* the round
time, and the two-pass schedule reads the payload twice (X for the mix,
mixed again for the aggregate).  Both equations are linear in ``X``, so

    agg = (tau^T A) @ X / m  =  w @ X,      w := (tau^T A) / m  (1, n)

and one streaming pass suffices: the grid walks payload chunks (the p
axis); each step loads an (n, pc) tile of ``X`` into VMEM **once**, keeps
``A`` (and the tiny precombined row ``w``) resident, and emits

  * the mixed tile ``A @ X_tile``            -- (n, pc), payload dtype
  * the aggregate row ``w @ X_tile``         -- (1, pc), float32

with float32 MXU accumulation for both regardless of payload dtype.

Two entry points:

``mix_aggregate_pallas``
    emits both outputs; HBM traffic ~2 n p B (read X once, write mixed +
    the (1, p) aggregate row) vs ~3 n p B for mix-then-aggregate.

``aggregate_pallas``
    exploits the identity to skip the mixed output entirely and write
    only the (1, p) row -- traffic ~n p B.  This is the right kernel for
    FedAvg (``A = I`` makes ``mixed`` redundant) and for server rounds
    that do not log per-client deltas.

Shape contract matches ``mixing.mix_pallas``: callers (``ops.py``) pad
``n`` to the float32 sublane multiple and ``p`` to a multiple of
``chunk``; ``w`` arrives padded to ``(_SUBLANE, n_pad)`` with the real
weights in row 0.  Validated in interpret mode on CPU against the
composed ``mix_ref`` + eq.-4 oracle (tests/test_fused_mixing.py); the
wrappers in ``ops.py`` select compiled lowering (``interpret=False``)
automatically on TPU (``repro.kernels.dispatch.default_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mixing import f32_matmul

__all__ = ["mix_aggregate_pallas", "aggregate_pallas", "dequant_tile",
           "mix_aggregate_dequant_pallas", "aggregate_dequant_pallas"]


def _fused_kernel(a_ref, w_ref, x_ref, mixed_ref, agg_ref):
    a = a_ref[...].astype(jnp.float32)          # (n_pad, n_pad), resident
    w = w_ref[...].astype(jnp.float32)          # (s, n_pad), resident
    x = x_ref[...].astype(jnp.float32)          # (n_pad, pc) -- read ONCE
    mixed_ref[...] = f32_matmul(a, x).astype(mixed_ref.dtype)
    agg_ref[...] = f32_matmul(w, x)


def _agg_kernel(w_ref, x_ref, agg_ref):
    w = w_ref[...].astype(jnp.float32)          # (s, n_pad), resident
    x = x_ref[...].astype(jnp.float32)          # (n_pad, pc) -- read ONCE
    agg_ref[...] = f32_matmul(w, x)


def mix_aggregate_pallas(A: jnp.ndarray, w: jnp.ndarray, X: jnp.ndarray, *,
                         chunk: int = 2048, interpret: bool = True):
    """One-pass fused mix + aggregate on hardware-aligned shapes.

    A (n_pad, n_pad); w (s, n_pad) with the precombined ``tau^T A / m``
    row in w[0]; X (n_pad, p_pad), p_pad % chunk == 0.  Returns
    ``(mixed, agg)``: (n_pad, p_pad) in X.dtype and (s, p_pad) float32.
    Padding/unpadding is the wrapper's job (ops.py).
    """
    n, p = X.shape
    s = w.shape[0]
    assert A.shape == (n, n), (A.shape, X.shape)
    assert w.shape == (s, n), (w.shape, X.shape)
    assert p % chunk == 0, (p, chunk)
    grid = (p // chunk,)
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((s, n), lambda i: (0, 0)),        # w resident
            pl.BlockSpec((n, chunk), lambda i: (0, i)),    # stream X once
        ],
        out_specs=[
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
            pl.BlockSpec((s, chunk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, p), X.dtype),
            jax.ShapeDtypeStruct((s, p), jnp.float32),
        ],
        interpret=interpret,
    )(A, w, X)


def aggregate_pallas(w: jnp.ndarray, X: jnp.ndarray, *, chunk: int = 2048,
                     interpret: bool = True) -> jnp.ndarray:
    """Aggregate-only variant: ``w @ X`` without materializing the mixed
    deltas (``sum_i tau_i (A X)_i = (tau^T A) X``).  w (s, n_pad) with the
    real row in w[0]; X (n_pad, p_pad).  Returns (s, p_pad) float32."""
    n, p = X.shape
    s = w.shape[0]
    assert w.shape == (s, n), (w.shape, X.shape)
    assert p % chunk == 0, (p, chunk)
    grid = (p // chunk,)
    return pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, n), lambda i: (0, 0)),        # w resident
            pl.BlockSpec((n, chunk), lambda i: (0, i)),    # stream X once
        ],
        out_specs=pl.BlockSpec((s, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s, p), jnp.float32),
        interpret=interpret,
    )(w, X)


# ---------------------------------------------------------------------------
# Quantized-payload variants: the SAME one-pass schedules, with a dequant
# epilogue fused in front of the fp32 matmuls.  The payload tile arrives in
# its wire format (int8 / nibble-packed int4 / fp8 -- ``repro.fl.packing
# .QuantSpec``), the tiny per-block fp32 scale tile rides along as a side
# operand, and the dequantized fp32 values exist only inside VMEM -- no
# dequantized (n, p) payload is ever materialized in HBM.  Mixed AND
# aggregate outputs are fp32 (the accumulator dtype): casting the mixed
# deltas back to a payload dtype is the caller's epilogue if it wants one.
# ---------------------------------------------------------------------------


def dequant_tile(x: jnp.ndarray, scales: jnp.ndarray, *, storage: str,
                 block: int) -> jnp.ndarray:
    """In-register dequant of one payload tile.

    ``x`` is the stored tile -- (n, pc) for int8/fp8, (n, pc // 2)
    nibble-packed int8 for 'int4' (each block's first half in the low
    nibbles, its second half in the high nibbles) -- ``scales`` the
    matching (n, pc // block) fp32 scale tile.  Returns the (n, pc) fp32
    values ``stored * scale``, the same arithmetic as
    ``repro.fl.packing.dequantize_group`` (host round-trips match the
    kernel path bitwise).  The int4 halves are put back with static
    lane-aligned slices (``block // 2`` is a multiple of 128), the
    unpack Mosaic lowers."""
    n = x.shape[0]
    if storage == "int4":
        x = x.astype(jnp.int32)   # Mosaic has no int8 shifts
        lo = (x << 28) >> 28      # sign-extend both nibbles of each byte
        hi = x >> 4
        h = block // 2
        v = jnp.concatenate(
            [half[:, b * h:(b + 1) * h]
             for b in range(x.shape[1] // h) for half in (lo, hi)],
            axis=1).astype(jnp.float32)
    else:
        v = x.astype(jnp.float32)
    nb = scales.shape[1]
    v = v.reshape(n, nb, block) * scales[:, :, None].astype(jnp.float32)
    return v.reshape(n, nb * block)


def _fused_dequant_kernel(a_ref, w_ref, x_ref, s_ref, mixed_ref, agg_ref,
                          *, storage, block):
    a = a_ref[...].astype(jnp.float32)          # (n_pad, n_pad), resident
    w = w_ref[...].astype(jnp.float32)          # (s, n_pad), resident
    x = dequant_tile(x_ref[...], s_ref[...], storage=storage, block=block)
    mixed_ref[...] = f32_matmul(a, x)
    agg_ref[...] = f32_matmul(w, x)


def _agg_dequant_kernel(w_ref, x_ref, s_ref, agg_ref, *, storage, block):
    w = w_ref[...].astype(jnp.float32)          # (s, n_pad), resident
    x = dequant_tile(x_ref[...], s_ref[...], storage=storage, block=block)
    agg_ref[...] = f32_matmul(w, x)


def _quant_grid(Xq, S, storage, block, chunk):
    """Shared shape plumbing for the dequant kernels: payload width in
    *value* columns, container columns per chunk, and the scales laid out
    one (n, chunk // block) tile per chunk -- ``(p // chunk, n, chunk //
    block)``, so the scale BlockSpec's trailing dims equal the array's
    (Mosaic refuses a (n, chunk // block) block of the (n, p // block)
    buffer: its lane dim is neither 128-divisible nor the whole axis)."""
    assert chunk % block == 0, (chunk, block)
    n, nb = S.shape
    p = nb * block                               # value columns
    qcols = chunk // 2 if storage == "int4" else chunk
    assert Xq.shape[1] * (2 if storage == "int4" else 1) == p, \
        (Xq.shape, S.shape, block)
    assert p % chunk == 0, (p, chunk)
    sb = chunk // block
    S_tiles = S.reshape(n, p // chunk, sb).transpose(1, 0, 2)
    return p, qcols, S_tiles, pl.BlockSpec((None, n, sb),
                                           lambda i: (i, 0, 0))


def mix_aggregate_dequant_pallas(A: jnp.ndarray, w: jnp.ndarray,
                                 Xq: jnp.ndarray, S: jnp.ndarray, *,
                                 storage: str, block: int,
                                 chunk: int = 2048, interpret: bool = True):
    """One-pass fused mix + aggregate over a quantized payload.

    A (n_pad, n_pad); w (s, n_pad) with the combine row in w[0]; Xq the
    stored containers (n_pad, p_pad * bits / 8); S the fp32 scales
    (n_pad, p_pad / block).  Returns ``(mixed, agg)``, both fp32:
    (n_pad, p_pad) and (s, p_pad)."""
    n = Xq.shape[0]
    s = w.shape[0]
    p, qcols, S_tiles, s_spec = _quant_grid(Xq, S, storage, block, chunk)
    assert A.shape == (n, n) and w.shape == (s, n), (A.shape, w.shape)
    grid = (p // chunk,)
    return pl.pallas_call(
        functools.partial(_fused_dequant_kernel, storage=storage,
                          block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),        # A resident
            pl.BlockSpec((s, n), lambda i: (0, 0)),        # w resident
            pl.BlockSpec((n, qcols), lambda i: (0, i)),    # stored payload
            s_spec,                                        # scale side buf
        ],
        out_specs=[
            pl.BlockSpec((n, chunk), lambda i: (0, i)),
            pl.BlockSpec((s, chunk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, p), jnp.float32),
            jax.ShapeDtypeStruct((s, p), jnp.float32),
        ],
        interpret=interpret,
    )(A, w, Xq, S_tiles)


def aggregate_dequant_pallas(w: jnp.ndarray, Xq: jnp.ndarray,
                             S: jnp.ndarray, *, storage: str, block: int,
                             chunk: int = 2048,
                             interpret: bool = True) -> jnp.ndarray:
    """Aggregate-only dequant variant: ``w @ dequant(Xq, S)`` streaming
    the *compressed* payload once; neither the mixed deltas nor the
    dequantized payload ever exist in HBM.  Returns (s, p_pad) fp32."""
    n = Xq.shape[0]
    s = w.shape[0]
    p, qcols, S_tiles, s_spec = _quant_grid(Xq, S, storage, block, chunk)
    assert w.shape == (s, n), (w.shape, Xq.shape)
    grid = (p // chunk,)
    return pl.pallas_call(
        functools.partial(_agg_dequant_kernel, storage=storage,
                          block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, n), lambda i: (0, 0)),        # w resident
            pl.BlockSpec((n, qcols), lambda i: (0, i)),    # stored payload
            s_spec,                                        # scale side buf
        ],
        out_specs=pl.BlockSpec((s, chunk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((s, p), jnp.float32),
        interpret=interpret,
    )(w, Xq, S_tiles)
