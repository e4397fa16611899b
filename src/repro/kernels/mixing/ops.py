"""Jitted wrappers for the graph-mixing kernels: shape padding, pytree
plumbing, and backend dispatch (compiled on TPU, interpret elsewhere --
see ``default_interpret``).

Entry points:

* ``mix`` / ``mix_pytree``       -- eq. 3 only (``Delta = A @ X``).
* ``mix_aggregate``              -- fused one-pass eq. 3 + eq. 4: mixed
                                    deltas plus the tau-weighted D2S
                                    aggregate row from a single streaming
                                    read of the payload.
* ``aggregate``                  -- aggregate-only fast path exploiting
                                    ``sum_i tau_i (A X)_i = (tau^T A) X``
                                    (FedAvg ``A = I``, or rounds that do
                                    not need per-client mixed deltas).
* ``mix_aggregate_grouped`` /    -- the same one-pass schedules over a
  ``aggregate_grouped``             dtype-grouped packed tree
                                    (``repro.fl.packing``): one fused
                                    launch per dtype group, the padded
                                    ``A`` and precombined weight row
                                    shared across launches, per-group
                                    fp32 aggregate rows returned for the
                                    epilogue concatenation.

Every ``interpret`` knob defaults to ``None`` = platform-resolved
(``default_interpret()``: compiled on TPU, interpreter on CPU/GPU,
``REPRO_PALLAS_INTERPRET`` env override) -- pass an explicit bool to pin
a mode.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import default_interpret, resolve_interpret
from .fused import (aggregate_dequant_pallas, aggregate_pallas,
                    mix_aggregate_dequant_pallas, mix_aggregate_pallas)
from .mixing import mix_pallas
from .ref import mix_ref

PyTree = Any

__all__ = ["mix", "mix_pytree", "mix_aggregate", "aggregate",
           "mix_aggregate_grouped", "aggregate_grouped",
           "combine_weights", "combine_weights_ell",
           "sparse_mix", "sparse_mix_aggregate", "sparse_aggregate",
           "sparse_mix_aggregate_grouped", "sparse_aggregate_grouped",
           "mix_aggregate_q", "aggregate_q",
           "mix_aggregate_grouped_q", "aggregate_grouped_q",
           "sparse_mix_aggregate_q", "sparse_aggregate_q",
           "sparse_mix_aggregate_grouped_q", "sparse_aggregate_grouped_q",
           "default_interpret"]

_LANE = 128
_SUBLANE = 8


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_inputs(A, X, chunk):
    """Pad (A, X) to TPU tile alignment; returns (A_p, X_p, n, p)."""
    n, p = X.shape
    n_pad = _pad_to(n, _SUBLANE)
    p_pad = _pad_to(p, chunk)
    A_p = jnp.zeros((n_pad, n_pad), A.dtype).at[:n, :n].set(A)
    X_p = jnp.zeros((n_pad, p_pad), X.dtype).at[:n, :p].set(X)
    return A_p, X_p, n, p


def combine_weights(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                    active: Optional[jnp.ndarray] = None,
                    weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Precombined D2S weight row ``w = (tau^T A) / m`` (fp32, shape (n,)).

    The algebraic identity ``(1/m) sum_i tau_i (A X)_i = w @ X`` is what
    every one-pass aggregate path (fused kernel, jit-level 'fused', the
    worker-sharded 'fused_rs') exploits; this is its single definition.

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
    For ``m != 0`` the guard is bitwise-inert.  The sparse definition
    (``combine_weights_ell``) shares the same guard.
    """
    tau = _fold_mask(tau, active, weights)
    w = jnp.einsum("i,ij->j", tau, A.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    w = _safe_divide_by_m(w, m)
    if active is not None:
        w = w * active.astype(jnp.float32)
    return w


def combine_weights_ell(idx: jnp.ndarray, w_ell: jnp.ndarray,
                        tau: jnp.ndarray, m: jnp.ndarray,
                        active: Optional[jnp.ndarray] = None,
                        weights: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """``combine_weights`` from the ELL form of ``A`` -- O(nnz), never
    densifying: ``w[j] = (1/m) sum_{(i,k): idx[i,k]=j} tau_i w_ell[i,k]``
    is a segment-sum over the stored entries (padding slots carry weight
    0.0, so their contribution to segment 0 vanishes).  Masking semantics
    and the ``m == 0`` guard are identical to the dense definition
    (allclose, not bitwise: the reduction order differs)."""
    tau = _fold_mask(tau, active, weights)
    contrib = tau[:, None] * w_ell.astype(jnp.float32)
    w = jax.ops.segment_sum(contrib.ravel(), idx.ravel(),
                            num_segments=tau.shape[0])
    w = _safe_divide_by_m(w, m)
    if active is not None:
        w = w * active.astype(jnp.float32)
    return w


def _fold_mask(tau, active, weights):
    """The shared upload-leg folding: tau * active * weights, fp32."""
    tau = tau.astype(jnp.float32)
    if active is not None:
        tau = tau * active.astype(jnp.float32)
    if weights is not None:
        tau = tau * jnp.asarray(weights, jnp.float32)
    return tau


def _safe_divide_by_m(w, m):
    """``w / m`` with ``m == 0 -> 0`` (see ``combine_weights``); bitwise
    ``w / m`` whenever ``m != 0``."""
    m = jnp.asarray(m, jnp.float32)
    zero = m == 0
    return jnp.where(zero, 0.0, w / jnp.where(zero, 1.0, m))


def _weight_row(A, tau, m, n_pad, active=None, weights=None):
    """``combine_weights`` padded to the sublane multiple with the real
    weights in row 0 (the layout the fused kernels consume)."""
    w = combine_weights(A, tau, m, active, weights)
    n = w.shape[0]
    return jnp.zeros((_SUBLANE, n_pad), jnp.float32).at[0, :n].set(w)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mix(A: jnp.ndarray, X: jnp.ndarray, *, chunk: int = 2048,
        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Delta = A @ X for arbitrary (n, p); pads to TPU tile alignment,
    runs the Pallas kernel, and slices back."""
    interpret = resolve_interpret(interpret)
    A_p, X_p, n, p = _pad_inputs(A, X, chunk)
    out = mix_pallas(A_p, X_p, chunk=chunk, interpret=interpret)
    return out[:n, :p]


def mix_pytree(A: jnp.ndarray, deltas: PyTree, *, chunk: int = 2048,
               interpret: Optional[bool] = None) -> PyTree:
    """Apply the mixing kernel to a pytree of per-client deltas (leaves with
    leading client axis n), flattening trailing dims per leaf.

    One kernel launch *per leaf*; the packed fused path
    (``repro.fl.packing`` + ``mix_aggregate``) replaces this loop with a
    single launch per dtype group."""
    def one(d):
        flat = d.reshape(d.shape[0], -1)
        return mix(A, flat, chunk=chunk,
                   interpret=interpret).reshape(d.shape)

    return jax.tree.map(one, deltas)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mix_aggregate(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                  X: jnp.ndarray, *, chunk: int = 2048,
                  interpret: Optional[bool] = None,
                  active: Optional[jnp.ndarray] = None,
                  weights: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused eq. 3 + eq. 4 over an arbitrary (n, p) payload.

    Returns ``(mixed, agg)``: mixed (n, p) in X.dtype and the float32
    aggregate row agg (p,) = ``(1/m) sum_i tau_i (A @ X)_i``, computed
    from one streaming pass over ``X``.

    ``active`` folds a straggler mask into the aggregate row and
    ``weights`` per-upload staleness discounts (see ``combine_weights``);
    the *mixed* output reflects dropped clients only if the caller
    already zeroed their rows of ``X`` (the payload is streamed as
    given).
    """
    interpret = resolve_interpret(interpret)
    A_p, X_p, n, p = _pad_inputs(A, X, chunk)
    w_p = _weight_row(A, tau, m, A_p.shape[0], active, weights)
    mixed, agg = mix_aggregate_pallas(A_p, w_p, X_p, chunk=chunk,
                                      interpret=interpret)
    return mixed[:n, :p], agg[0, :p]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def aggregate(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
              X: jnp.ndarray, *, chunk: int = 2048,
              interpret: Optional[bool] = None,
              active: Optional[jnp.ndarray] = None,
              weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Aggregate-only fast path: the float32 row
    ``(1/m) sum_i tau_i (A @ X)_i = ((tau^T A) / m) @ X`` (p,), reading
    ``X`` once and never materializing the mixed deltas.  A straggler
    mask (``active``) or staleness discount (``weights``) costs nothing
    here: both are folded into the combine row, the payload is
    untouched."""
    interpret = resolve_interpret(interpret)
    A_p, X_p, n, p = _pad_inputs(A, X, chunk)
    w_p = _weight_row(A, tau, m, A_p.shape[0], active, weights)
    agg = aggregate_pallas(w_p, X_p, chunk=chunk, interpret=interpret)
    return agg[0, :p]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mix_aggregate_grouped(A: jnp.ndarray, tau: jnp.ndarray,
                          m: jnp.ndarray,
                          bufs: Tuple[jnp.ndarray, ...], *,
                          chunk: int = 2048,
                          interpret: Optional[bool] = None,
                          active: Optional[jnp.ndarray] = None,
                          weights: Optional[jnp.ndarray] = None
                          ) -> Tuple[Tuple[jnp.ndarray, ...],
                                     Tuple[jnp.ndarray, ...]]:
    """Fused eq. 3 + eq. 4 over a dtype-grouped packed tree: one fused
    kernel launch per group buffer, each streamed at its native dtype.

    ``bufs`` is ``repro.fl.packing.pack``'s output (per-group (n, P_g)
    buffers).  Returns ``(mixed_bufs, agg_rows)``: per-group mixed
    buffers in the group dtypes and per-group fp32 aggregate rows, ready
    for ``packing.unpack`` / ``packing.apply_aggregate_row``.
    """
    out = [mix_aggregate(A, tau, m, b, chunk=chunk, interpret=interpret,
                         active=active, weights=weights)
           for b in bufs]
    return tuple(mb for mb, _ in out), tuple(r for _, r in out)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def aggregate_grouped(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                      bufs: Tuple[jnp.ndarray, ...], *, chunk: int = 2048,
                      interpret: Optional[bool] = None,
                      active: Optional[jnp.ndarray] = None,
                      weights: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, ...]:
    """Aggregate-only variant of ``mix_aggregate_grouped``: per-group
    fp32 rows ``((tau^T A) / m) @ X_g``, one launch per dtype group, the
    mixed deltas never materialized."""
    return tuple(aggregate(A, tau, m, b, chunk=chunk, interpret=interpret,
                           active=active, weights=weights)
                 for b in bufs)


# --------------------------------------------------------------------------
# Quantized-payload entry points (``_q`` suffix): the same one-pass
# schedules over a wire-format payload (``repro.fl.packing.QuantSpec``) --
# stored containers + fp32 per-block scale side buffers in, fp32 mixed /
# aggregate out, dequantization fused into the kernels' VMEM epilogue.
# ``quant`` is the (hashable, jit-static) QuantSpec.
# --------------------------------------------------------------------------


def _check_quant_chunk(quant, chunk: int) -> None:
    if chunk % quant.block:
        raise ValueError(
            f"chunk ({chunk}) must be a multiple of quant.block "
            f"({quant.block}) so every payload tile covers whole scale "
            "blocks")


def _pad_quant_inputs(Xq, S, quant, chunk):
    """Pad the stored payload + scales to TPU tile alignment.  Padded
    container columns are zero bytes (two zero nibbles for int4) and
    padded scale blocks are 0.0, so the padding dequantizes to exact
    zeros.  Returns ``(Xq_p, S_p, n, p)`` with ``p`` the real *value*
    column count."""
    n, pq = Xq.shape
    p = S.shape[1] * quant.block
    n_pad = _pad_to(n, _SUBLANE)
    p_pad = _pad_to(p, chunk)
    Xq_p = jnp.zeros((n_pad, quant.stored_cols(p_pad)),
                     Xq.dtype).at[:n, :pq].set(Xq)
    S_p = jnp.zeros((n_pad, p_pad // quant.block),
                    jnp.float32).at[:n, :S.shape[1]].set(S)
    return Xq_p, S_p, n, p


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def mix_aggregate_q(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                    Xq: jnp.ndarray, S: jnp.ndarray, *, quant,
                    chunk: int = 2048, interpret: Optional[bool] = None,
                    active: Optional[jnp.ndarray] = None,
                    weights: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused eq. 3 + eq. 4 over one quantized group buffer.

    ``Xq`` (n, P * bits / 8) stored containers, ``S`` (n, P / block)
    fp32 scales (``repro.fl.packing.quantize_group``).  Returns
    ``(mixed, agg)``: the fp32 (n, P) mixed deltas and the fp32 (P,)
    aggregate row.  A straggler mask zeroes dropped clients out of the
    *aggregate* leg only (combine row); callers that also need masked
    mixed output zero the dropped rows of ``S`` first -- one multiply on
    the tiny scale buffer, never on the payload."""
    _check_quant_chunk(quant, chunk)
    interpret = resolve_interpret(interpret)
    Xq_p, S_p, n, p = _pad_quant_inputs(Xq, S, quant, chunk)
    n_pad = Xq_p.shape[0]
    A_p = jnp.zeros((n_pad, n_pad), A.dtype).at[:n, :n].set(A)
    w_p = _weight_row(A, tau, m, n_pad, active, weights)
    mixed, agg = mix_aggregate_dequant_pallas(
        A_p, w_p, Xq_p, S_p, storage=quant.storage, block=quant.block,
        chunk=chunk, interpret=interpret)
    return mixed[:n, :p], agg[0, :p]


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def aggregate_q(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                Xq: jnp.ndarray, S: jnp.ndarray, *, quant,
                chunk: int = 2048, interpret: Optional[bool] = None,
                active: Optional[jnp.ndarray] = None,
                weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Aggregate-only fast path over one quantized group buffer: the
    fp32 row ``((tau^T A)/m) @ dequant(Xq, S)`` (P,), streaming the
    compressed payload once -- neither the mixed deltas nor a
    dequantized payload ever exist."""
    _check_quant_chunk(quant, chunk)
    interpret = resolve_interpret(interpret)
    Xq_p, S_p, n, p = _pad_quant_inputs(Xq, S, quant, chunk)
    w_p = _weight_row(A, tau, m, Xq_p.shape[0], active, weights)
    agg = aggregate_dequant_pallas(
        w_p, Xq_p, S_p, storage=quant.storage, block=quant.block,
        chunk=chunk, interpret=interpret)
    return agg[0, :p]


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def mix_aggregate_grouped_q(A: jnp.ndarray, tau: jnp.ndarray,
                            m: jnp.ndarray,
                            stored: Tuple[jnp.ndarray, ...],
                            scales: Tuple[jnp.ndarray, ...], *, quant,
                            chunk: int = 2048,
                            interpret: Optional[bool] = None,
                            active: Optional[jnp.ndarray] = None,
                            weights: Optional[jnp.ndarray] = None
                            ) -> Tuple[Tuple[jnp.ndarray, ...],
                                       Tuple[jnp.ndarray, ...]]:
    """``mix_aggregate_grouped`` over the wire format: one fused
    dequant launch per group (``repro.fl.packing.quantize_packed``
    output).  Returns per-group fp32 ``(mixed_bufs, agg_rows)``."""
    out = [mix_aggregate_q(A, tau, m, xq, s, quant=quant, chunk=chunk,
                           interpret=interpret, active=active,
                           weights=weights)
           for xq, s in zip(stored, scales)]
    return tuple(mb for mb, _ in out), tuple(r for _, r in out)


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def aggregate_grouped_q(A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                        stored: Tuple[jnp.ndarray, ...],
                        scales: Tuple[jnp.ndarray, ...], *, quant,
                        chunk: int = 2048,
                        interpret: Optional[bool] = None,
                        active: Optional[jnp.ndarray] = None,
                        weights: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, ...]:
    """``aggregate_grouped`` over the wire format: per-group fp32 rows,
    one aggregate-dequant launch per group."""
    return tuple(aggregate_q(A, tau, m, xq, s, quant=quant, chunk=chunk,
                             interpret=interpret, active=active,
                             weights=weights)
                 for xq, s in zip(stored, scales))


# --------------------------------------------------------------------------
# Sparse (ELL) entry points -- A as padded neighbor lists
# (``repro.core.sparse.SparseA.ell()``), never an (n, n) array: ``idx`` /
# ``w`` (n, d_max) hold each destination row's in-neighbor ids and
# ``A[i, j]`` weights, padding slots carry index 0 / weight 0.0 (a
# gather scaled by zero, a no-op needing no mask).  Eq. 3 is d_max row
# gathers in XLA (``_ell_mix``; Mosaic cannot lower a dynamic row gather
# inside a kernel), and the eq.-4 aggregate is the combine row built by
# an O(nnz) segment-sum (``combine_weights_ell``) applied by the dense
# aggregate kernels.  Work is O(n d_max p); nothing is (n, n).
# --------------------------------------------------------------------------


def _ell_mix(idx, w, X):
    """``mixed[i] = sum_k w[i, k] * X[idx[i, k]]`` -- an fp32 (n, p)
    accumulator, neighbor slots added in order.  allclose (not bitwise)
    to the dense ``A @ X``: the reduction order differs."""
    w = w.astype(jnp.float32)

    def add(k, acc):
        return acc + w[:, k, None] * X[idx[:, k]].astype(jnp.float32)

    return jax.lax.fori_loop(0, idx.shape[1], add,
                             jnp.zeros(X.shape, jnp.float32))


def _sparse_weight_row(idx, w_ell, tau, m, n_pad, active=None,
                       weights=None):
    """``combine_weights_ell`` padded to the fused-kernel layout (real
    weights in row 0 of an ``(_SUBLANE, n_pad)`` block)."""
    wrow = combine_weights_ell(idx, w_ell, tau, m, active, weights)
    n = wrow.shape[0]
    return jnp.zeros((_SUBLANE, n_pad), jnp.float32).at[0, :n].set(wrow)


@jax.jit
def sparse_mix(idx: jnp.ndarray, w: jnp.ndarray,
               X: jnp.ndarray) -> jnp.ndarray:
    """Sparse ``Delta = A @ X`` for arbitrary (n, p), in X.dtype:
    O(n d_max p) work.  allclose to the dense ``mix`` (fp32 accumulation
    both sides; reduction order differs)."""
    return _ell_mix(idx, w, X).astype(X.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def sparse_mix_aggregate(idx: jnp.ndarray, w: jnp.ndarray,
                         tau: jnp.ndarray, m: jnp.ndarray,
                         X: jnp.ndarray, *, chunk: int = 2048,
                         interpret: Optional[bool] = None,
                         active: Optional[jnp.ndarray] = None,
                         weights: Optional[jnp.ndarray] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse eq. 3 + eq. 4: the mixed payload (X.dtype) and the fp32
    aggregate row, with the combine row built by segment-sum (O(nnz)).
    Mask/weight semantics match ``mix_aggregate``."""
    agg = sparse_aggregate(idx, w, tau, m, X, chunk=chunk,
                           interpret=interpret, active=active,
                           weights=weights)
    return sparse_mix(idx, w, X), agg


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def sparse_aggregate(idx: jnp.ndarray, w: jnp.ndarray, tau: jnp.ndarray,
                     m: jnp.ndarray, X: jnp.ndarray, *, chunk: int = 2048,
                     interpret: Optional[bool] = None,
                     active: Optional[jnp.ndarray] = None,
                     weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Sparse aggregate-only fast path: the combine row is a segment-sum
    over the ELL entries, after which ``w @ X`` is an ordinary dense
    vector-matrix kernel (``fused.aggregate_pallas``) -- nothing
    (n, n)."""
    interpret = resolve_interpret(interpret)
    n, p = X.shape
    n_pad = _pad_to(n, _SUBLANE)
    X_p = jnp.zeros((n_pad, _pad_to(p, chunk)), X.dtype).at[:n, :p].set(X)
    wrow_p = _sparse_weight_row(idx, w, tau, m, n_pad, active, weights)
    agg = aggregate_pallas(wrow_p, X_p, chunk=chunk, interpret=interpret)
    return agg[0, :p]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def sparse_mix_aggregate_grouped(idx: jnp.ndarray, w: jnp.ndarray,
                                 tau: jnp.ndarray, m: jnp.ndarray,
                                 bufs: Tuple[jnp.ndarray, ...], *,
                                 chunk: int = 2048,
                                 interpret: Optional[bool] = None,
                                 active: Optional[jnp.ndarray] = None,
                                 weights: Optional[jnp.ndarray] = None
                                 ) -> Tuple[Tuple[jnp.ndarray, ...],
                                            Tuple[jnp.ndarray, ...]]:
    """``mix_aggregate_grouped`` on the ELL form, one dtype group at a
    time."""
    out = [sparse_mix_aggregate(idx, w, tau, m, b, chunk=chunk,
                                interpret=interpret, active=active,
                                weights=weights)
           for b in bufs]
    return tuple(mb for mb, _ in out), tuple(r for _, r in out)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def sparse_aggregate_grouped(idx: jnp.ndarray, w: jnp.ndarray,
                             tau: jnp.ndarray, m: jnp.ndarray,
                             bufs: Tuple[jnp.ndarray, ...], *,
                             chunk: int = 2048,
                             interpret: Optional[bool] = None,
                             active: Optional[jnp.ndarray] = None,
                             weights: Optional[jnp.ndarray] = None
                             ) -> Tuple[jnp.ndarray, ...]:
    """``aggregate_grouped`` on the ELL form: per-group fp32 rows, the
    mixed deltas never materialized, nothing (n, n)."""
    return tuple(sparse_aggregate(idx, w, tau, m, b, chunk=chunk,
                                  interpret=interpret, active=active,
                                  weights=weights)
                 for b in bufs)


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def sparse_mix_aggregate_q(idx: jnp.ndarray, w: jnp.ndarray,
                           tau: jnp.ndarray, m: jnp.ndarray,
                           Xq: jnp.ndarray, S: jnp.ndarray, *, quant,
                           chunk: int = 2048,
                           interpret: Optional[bool] = None,
                           active: Optional[jnp.ndarray] = None,
                           weights: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sparse eq. 3 + eq. 4 over one quantized group buffer: the ELL
    gather mixes the dequantized fp32 payload, the aggregate leg streams
    the wire format through the dequant kernel.  Mask/weight semantics
    match ``mix_aggregate_q``."""
    # deferred: repro.fl imports the round layer, which imports this module
    from repro.fl.packing import dequantize_group

    agg = sparse_aggregate_q(idx, w, tau, m, Xq, S, quant=quant,
                             chunk=chunk, interpret=interpret,
                             active=active, weights=weights)
    return _ell_mix(idx, w, dequantize_group(Xq, S, quant)), agg


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def sparse_aggregate_q(idx: jnp.ndarray, w: jnp.ndarray, tau: jnp.ndarray,
                       m: jnp.ndarray, Xq: jnp.ndarray, S: jnp.ndarray, *,
                       quant, chunk: int = 2048,
                       interpret: Optional[bool] = None,
                       active: Optional[jnp.ndarray] = None,
                       weights: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Sparse aggregate-only dequant path: the combine row is the same
    O(nnz) segment-sum, after which the aggregate-dequant kernel streams
    the compressed payload -- no sparse kernel needed."""
    _check_quant_chunk(quant, chunk)
    interpret = resolve_interpret(interpret)
    Xq_p, S_p, n, p = _pad_quant_inputs(Xq, S, quant, chunk)
    wrow_p = _sparse_weight_row(idx, w, tau, m, Xq_p.shape[0], active,
                                weights)
    agg = aggregate_dequant_pallas(
        wrow_p, Xq_p, S_p, storage=quant.storage, block=quant.block,
        chunk=chunk, interpret=interpret)
    return agg[0, :p]


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def sparse_mix_aggregate_grouped_q(idx: jnp.ndarray, w: jnp.ndarray,
                                   tau: jnp.ndarray, m: jnp.ndarray,
                                   stored: Tuple[jnp.ndarray, ...],
                                   scales: Tuple[jnp.ndarray, ...], *,
                                   quant, chunk: int = 2048,
                                   interpret: Optional[bool] = None,
                                   active: Optional[jnp.ndarray] = None,
                                   weights: Optional[jnp.ndarray] = None
                                   ) -> Tuple[Tuple[jnp.ndarray, ...],
                                              Tuple[jnp.ndarray, ...]]:
    """``sparse_mix_aggregate_grouped`` over the wire format."""
    out = [sparse_mix_aggregate_q(idx, w, tau, m, xq, s, quant=quant,
                                  chunk=chunk, interpret=interpret,
                                  active=active, weights=weights)
           for xq, s in zip(stored, scales)]
    return tuple(mb for mb, _ in out), tuple(r for _, r in out)


@functools.partial(jax.jit, static_argnames=("quant", "chunk", "interpret"))
def sparse_aggregate_grouped_q(idx: jnp.ndarray, w: jnp.ndarray,
                               tau: jnp.ndarray, m: jnp.ndarray,
                               stored: Tuple[jnp.ndarray, ...],
                               scales: Tuple[jnp.ndarray, ...], *, quant,
                               chunk: int = 2048,
                               interpret: Optional[bool] = None,
                               active: Optional[jnp.ndarray] = None,
                               weights: Optional[jnp.ndarray] = None
                               ) -> Tuple[jnp.ndarray, ...]:
    """``sparse_aggregate_grouped`` over the wire format."""
    return tuple(sparse_aggregate_q(idx, w, tau, m, xq, s, quant=quant,
                                    chunk=chunk, interpret=interpret,
                                    active=active, weights=weights)
                 for xq, s in zip(stored, scales))
