"""The graph-mixing entry points: eq. 3 (D2D mix) and eq. 4 (D2S
aggregate) over a dtype-grouped packed payload, one kernel launch per
group buffer.

* ``combine_weights``       -- the precombined D2S row ``(tau^T A) / m``.
* ``aggregate_grouped``     -- eq. 4 only: per-group fp32 rows
                               ``w @ X_g``, the mixed deltas never
                               materialized (``sum_i tau_i (A X)_i =
                               (tau^T A) X``).
* ``mix_aggregate_grouped`` -- eq. 3 + eq. 4: the mixed group buffers and
                               the same aggregate rows.

``A`` comes in one of two forms, told apart by its type:

* dense -- an ``(n, n)`` array.  The mix runs in the fused Pallas kernel
  (``fused.mix_aggregate_pallas``), which streams each payload tile once
  and emits the mixed tile and the aggregate row together.
* ELL   -- the pair ``(idx, w)`` of (n, d_max) arrays
  (``repro.core.sparse.SparseA.ell()``): each destination row's
  in-neighbor ids and ``A[i, j]`` weights, padding slots index 0 with
  weight 0.0.  The mix is d_max row gathers in XLA (``_ell_mix``; Mosaic
  cannot lower a dynamic row gather inside a kernel) and the combine row
  an O(nnz) segment-sum.  Nothing is (n, n).

Either way the aggregate leg is the dense ``fused.aggregate_pallas``
kernel applied to the combine row.

``bufs`` is ``repro.fl.packing.pack``'s output (per-group (n, P_g)
buffers).  With ``quant`` (a hashable ``repro.fl.packing.QuantSpec``)
the buffers are the wire format instead -- stored containers with fp32
per-block ``scales`` (``packing.quantize_packed``) -- and the dequant
kernels (``fused.*_dequant_pallas``) unpack each tile in VMEM.

Padding: ``n`` goes up to the sublane multiple (8), ``P`` up to a
multiple of ``chunk``, and the combine row sits in row 0 of an
``(8, n_pad)`` block; padded payload and scales are zeros, so they
contribute nothing.

``interpret=None`` resolves per platform (``repro.kernels.dispatch``:
compiled on TPU, the interpreter elsewhere, ``REPRO_PALLAS_INTERPRET``
overrides); pass a bool to pin a mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_interpret
from .fused import (aggregate_dequant_pallas, aggregate_pallas, dequant_tile,
                    mix_aggregate_dequant_pallas, mix_aggregate_pallas)

__all__ = ["combine_weights", "aggregate_grouped", "mix_aggregate_grouped"]

_SUBLANE = 8


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _is_ell(A) -> bool:
    return isinstance(A, (tuple, list))


def combine_weights(A, tau: jnp.ndarray, m: jnp.ndarray,
                    active: Optional[jnp.ndarray] = None,
                    weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Precombined D2S weight row ``w = (tau^T A) / m`` (fp32, shape (n,)).

    The algebraic identity ``(1/m) sum_i tau_i (A X)_i = w @ X`` is what
    every one-pass aggregate path (the aggregate kernels, the mesh
    'fused' / 'fused_rs' schedules) exploits; this is its single
    definition.  ``A`` is dense ``(n, n)`` or the ELL pair ``(idx, w)``;
    the ELL row is a segment-sum over the stored entries
    ``w[j] = (1/m) sum_{(i,k): idx[i,k]=j} tau_i w_ell[i,k]`` (padding
    slots carry weight 0.0, so their share of segment 0 vanishes) --
    allclose, not bitwise, to the dense row: the reduction order differs.

    ``active`` is the optional (n,) 0/1 straggler mask (``RoundPlan``
    ``active_t`` column): a dropped client neither uploads (its row of
    ``tau`` is zeroed) nor contributes a delta to its neighbors (its
    *column* of the combine row is zeroed) -- algebraically identical to
    zeroing its payload row, without touching the payload.  ``m`` must
    already be the effective sampled-and-active count (the plan's
    renormalized ``m_t``).  An all-ones mask is bitwise-identical to
    passing ``active=None``.

    ``weights`` is an optional per-upload discount (scalar or (n,) fp32,
    e.g. the semi-async staleness weight): it scales the *upload* leg
    only -- multiplied into ``tau``, never into the D2D contribution
    columns -- matching the sampled-to-sampled framing in which a stale
    client's own report is discounted but the fresh neighbor deltas it
    relayed are not.  ``m`` must then be the weighted divisor (the sum of
    accepted upload weights).  ``weights = 1.0`` is bitwise-identical to
    passing ``weights=None`` (IEEE ``x * 1.0 == x``), so the synchronous
    path is the exact degenerate case.

    ``m == 0`` (every sampled client dropped / faulted out of a round)
    safely yields the all-zero row -- the round contributes nothing to
    the server model -- instead of an inf/nan row poisoning the scan.
    For ``m != 0`` the guard is bitwise-inert.
    """
    tau = tau.astype(jnp.float32)
    if active is not None:
        tau = tau * active.astype(jnp.float32)
    if weights is not None:
        tau = tau * jnp.asarray(weights, jnp.float32)
    if _is_ell(A):
        idx, w_ell = A
        contrib = tau[:, None] * w_ell.astype(jnp.float32)
        w = jax.ops.segment_sum(contrib.ravel(), idx.ravel(),
                                num_segments=tau.shape[0])
    else:
        w = jnp.einsum("i,ij->j", tau, A.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    m = jnp.asarray(m, jnp.float32)
    zero = m == 0
    w = jnp.where(zero, 0.0, w / jnp.where(zero, 1.0, m))
    if active is not None:
        w = w * active.astype(jnp.float32)
    return w


def _check_quant_chunk(quant, chunk: int) -> None:
    if chunk % quant.block:
        raise ValueError(
            f"chunk ({chunk}) must be a multiple of quant.block "
            f"({quant.block}) so every payload tile covers whole scale "
            "blocks")


def _pad(X, S, quant, chunk):
    """Pad one group buffer (and, quantized, its scales) to TPU tile
    alignment.  Padded container columns are zero bytes (two zero nibbles
    for int4) and padded scale blocks 0.0, so the padding dequantizes to
    exact zeros.  Returns ``(X_p, S_p, p)`` with ``p`` the real *value*
    column count (``S_p`` is None for an fp payload)."""
    n, cols = X.shape
    n_pad = _pad_to(n, _SUBLANE)
    p = cols if quant is None else S.shape[1] * quant.block
    p_pad = _pad_to(p, chunk)
    stored = p_pad if quant is None else quant.stored_cols(p_pad)
    X_p = jnp.zeros((n_pad, stored), X.dtype).at[:n, :cols].set(X)
    if quant is None:
        return X_p, None, p
    S_p = jnp.zeros((n_pad, p_pad // quant.block),
                    jnp.float32).at[:n, :S.shape[1]].set(S)
    return X_p, S_p, p


def _setup(A, tau, m, bufs, scales, quant, chunk, interpret, active,
           weights):
    """What both entry points share: the chunk check, the resolved
    interpret mode, the per-group scales (None for fp), and the padded
    combine row with the real weights in row 0 of an ``(8, n_pad)``
    block (the layout the kernels consume)."""
    if quant is not None:
        _check_quant_chunk(quant, chunk)
    if scales is None:
        scales = (None,) * len(bufs)
    w = combine_weights(A, tau, m, active, weights)
    n = w.shape[0]
    w_p = jnp.zeros((_SUBLANE, _pad_to(n, _SUBLANE)),
                    jnp.float32).at[0, :n].set(w)
    return resolve_interpret(interpret), tuple(scales), w_p


def _aggregate_rows(w_p, bufs, scales, quant, chunk, interpret):
    rows = []
    for X, S in zip(bufs, scales):
        X_p, S_p, p = _pad(X, S, quant, chunk)
        if quant is None:
            agg = aggregate_pallas(w_p, X_p, chunk=chunk,
                                   interpret=interpret)
        else:
            agg = aggregate_dequant_pallas(
                w_p, X_p, S_p, storage=quant.storage, block=quant.block,
                chunk=chunk, interpret=interpret)
        rows.append(agg[0, :p])
    return tuple(rows)


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def aggregate_grouped(A, tau: jnp.ndarray, m: jnp.ndarray,
                      bufs: Tuple[jnp.ndarray, ...], *,
                      scales: Optional[Tuple[jnp.ndarray, ...]] = None,
                      quant=None, chunk: int = 2048,
                      interpret: Optional[bool] = None,
                      active: Optional[jnp.ndarray] = None,
                      weights: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, ...]:
    """Eq. 4 without eq. 3: the per-group fp32 rows
    ``((tau^T A) / m) @ X_g``, one aggregate launch per group, reading
    each payload once.  A straggler mask (``active``) or staleness
    discount (``weights``) costs nothing here: both fold into the
    combine row (``combine_weights``) and the payload is untouched.
    With ``quant`` the launch streams the compressed payload; neither
    the mixed deltas nor a dequantized payload ever exist."""
    interpret, scales, w_p = _setup(A, tau, m, bufs, scales, quant, chunk,
                                    interpret, active, weights)
    return _aggregate_rows(w_p, bufs, scales, quant, chunk, interpret)


def _ell_mix(idx, w, X):
    """``mixed[i] = sum_k w[i, k] * X[idx[i, k]]`` -- an fp32 (n, p)
    accumulator, neighbor slots added in order.  allclose (not bitwise)
    to the dense ``A @ X``: the reduction order differs."""
    w = w.astype(jnp.float32)

    def add(k, acc):
        return acc + w[:, k, None] * X[idx[:, k]].astype(jnp.float32)

    return jax.lax.fori_loop(0, idx.shape[1], add,
                             jnp.zeros(X.shape, jnp.float32))


def _dequant(Xq, S, quant):
    """The fp32 ``(n, P)`` payload of one stored group buffer, through the
    kernels' own ``dequant_tile``: one row per (client, scale block), so
    the tile holds a single block whatever ``P`` is."""
    n, nb = S.shape
    v = dequant_tile(Xq.reshape(n * nb, -1), S.reshape(n * nb, 1),
                     storage=quant.storage, block=quant.block)
    return v.reshape(n, nb * quant.block)


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def mix_aggregate_grouped(A, tau: jnp.ndarray, m: jnp.ndarray,
                          bufs: Tuple[jnp.ndarray, ...], *,
                          scales: Optional[Tuple[jnp.ndarray, ...]] = None,
                          quant=None, chunk: int = 2048,
                          interpret: Optional[bool] = None,
                          active: Optional[jnp.ndarray] = None,
                          weights: Optional[jnp.ndarray] = None
                          ) -> Tuple[Tuple[jnp.ndarray, ...],
                                     Tuple[jnp.ndarray, ...]]:
    """Eq. 3 + eq. 4: ``(mixed_bufs, agg_rows)``, the mixed group buffers
    and ``aggregate_grouped``'s rows, ready for ``packing.unpack`` /
    ``packing.apply_aggregate_row``.

    The mixed buffers keep the payload dtype for an fp payload and are
    fp32 for a quantized one.  Dense ``A`` runs one fused launch per
    group, streaming each payload once for both outputs; ELL ``A`` mixes
    by row gathers and runs the aggregate launch beside it.  Masks and
    weights reach the aggregate leg only (combine row): a caller that
    wants the mixed output to drop a client zeroes its rows of the
    payload, or of ``scales`` when quantized, first.
    """
    interpret, scales, w_p = _setup(A, tau, m, bufs, scales, quant, chunk,
                                    interpret, active, weights)
    if _is_ell(A):
        idx, w = A
        if quant is None:
            mixed = tuple(_ell_mix(idx, w, X).astype(X.dtype) for X in bufs)
        else:
            mixed = tuple(_ell_mix(idx, w, _dequant(X, S, quant))
                          for X, S in zip(bufs, scales))
        return mixed, _aggregate_rows(w_p, bufs, scales, quant, chunk,
                                      interpret)
    n = A.shape[0]
    n_pad = w_p.shape[1]
    A_p = jnp.zeros((n_pad, n_pad), A.dtype).at[:n, :n].set(A)
    mixed, rows = [], []
    for X, S in zip(bufs, scales):
        X_p, S_p, p = _pad(X, S, quant, chunk)
        if quant is None:
            mx, agg = mix_aggregate_pallas(A_p, w_p, X_p, chunk=chunk,
                                           interpret=interpret)
        else:
            mx, agg = mix_aggregate_dequant_pallas(
                A_p, w_p, X_p, S_p, storage=quant.storage,
                block=quant.block, chunk=chunk, interpret=interpret)
        mixed.append(mx[:n, :p])
        rows.append(agg[0, :p])
    return tuple(mixed), tuple(rows)
