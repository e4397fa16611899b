"""Jitted round functions for Algorithm 1 (single-host reference runtime).

This module implements one *global aggregation round* exactly as in the
paper, vectorized over clients with ``jax.vmap``:

    1. every client runs ``T`` local SGD iterations from the global model
       (eq. 1, Alg. 1 lines 2-5);
    2. clients exchange scaled cumulative gradients and compute the
       equal-neighbor weighted sums ``Delta = A @ X_diff`` (eq. 2-3,
       Alg. 1 lines 6-7);
    3. the PS aggregates the sampled deltas
       ``x <- x + (1/m) sum_i tau_i Delta_i`` (eq. 4, Alg. 1 line 9).

Everything topology- and sampling-dependent (``A``, ``tau``, ``m``, ``eta``)
enters as *runtime arrays*, so one compiled round serves all rounds of all
three algorithms (Alg. 1, FedAvg via ``A = I``, COLREL via fixed ``m``).

Steps 2+3 are the memory-bound hot path and come in interchangeable
backends (``make_round_fn(..., mixing_backend=...)``):

  'einsum'    -- leaf-wise jnp (``mix_deltas`` + ``global_update``); the
                 reference oracle.  fp32 accumulation regardless of delta
                 dtype, matching the Pallas kernels.
  'fused'     -- packed one-pass path: the delta pytree is flattened into
                 per-dtype lane-aligned (n, P_pad_g) buffers
                 (``repro.fl.packing``) and the fused kernel streams each
                 ONCE at its native dtype, emitting both the mixed deltas
                 (eq. 3) and the tau-weighted aggregate rows (eq. 4) in
                 one launch per dtype group (mixed bf16/fp32 trees never
                 promote to fp32 on the wire).
  'aggregate' -- aggregate-only fast path: same packed buffers, but the
                 kernel computes only ``((tau^T A)/m) @ X_g`` -- the mixed
                 deltas are never materialized and the round returns
                 ``None`` in their place (~3x less payload traffic than
                 two-pass; see BENCH_mixing.json).  The ``FederatedServer``
                 selects this automatically when nothing records
                 per-client mixed deltas.
  'sparse' / 'sparse_aggregate' -- the same two legs with ``A`` as the
                 ELL (neighbor-list) 2-tuple ``(idx, w)`` of (n, d_max)
                 arrays (``repro.core.sparse.SparseA.ell()``) instead of
                 an (n, n) matrix: eq. 3 runs as d_max row gathers and the
                 eq.-4 combine row is a segment-sum over the same entries.
                 O(n d_max p) work and O(n d_max) topology storage -- the
                 only backends that scale n past the dense O(n^2) wall.
                 allclose (not bitwise) to 'einsum': fp32 accumulation
                 both sides, reduction order differs.

Every backend but 'einsum' goes through ``kernels.mixing.ops``
(``mix_aggregate_grouped`` / ``aggregate_grouped``), which tells the two
forms of ``A`` apart by type.  Every backend also takes a quantized
payload (``quant``): the kernels dequantize in VMEM, the einsum oracle
on the host side of the wire.

Cohort forms (``make_round_fn(..., cohort=...)``): 'vmap' (the default)
trains all n clients at once under ``jax.vmap``, which keeps about
``(1 + 2n)`` param copies live.  'sequential' trains them one at a time
under ``jax.lax.scan`` over clients: each client runs its T steps from
the global params, and an fp32 accumulator adds ``w_j * Delta_j`` with
``w = (tau^T A) / m`` (``kernels.mixing.ops.combine_weights``, the
eq.-4 combine row the aggregate kernels use) as each client finishes;
the round then applies it to the global params.  About five param
copies stay live whatever n is.  It serves the aggregate-only backends
('aggregate', 'sparse_aggregate') without quantization: the mixed
deltas are never formed.  ``repro.fl.engine.LocalEngine`` picks the form
from the params' bytes and the device's memory.

``make_scanned_rounds`` wraps the round in ``jax.lax.scan`` over stacked
``(A_t, tau_t, m_t, eta_t[, active_t])`` sequences so a K-round
trajectory dispatches to the device once instead of once per round.

Straggler masks: every round function takes an optional ``active`` (n,)
0/1 mask (the ``RoundPlan`` ``active_t`` column).  A dropped client
contributes zero delta to its D2D neighbors and never uploads; the eq.-4
divisor ``m`` must then be the effective sampled-and-active count (the
plan renormalizes it).  The kernel backends fold the mask into the
``(tau^T A)/m`` combine row (``kernels.mixing.ops.combine_weights``) so
the aggregate-only path pays nothing for it; an all-ones mask is
bitwise-identical to ``active=None``.

The multi-device shard_map implementation with the same semantics lives in
``repro.fl.distributed``; this reference version doubles as its oracle.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "local_sgd",
    "client_deltas",
    "mix_deltas",
    "global_update",
    "mask_clients",
    "make_round_fn",
    "make_scanned_rounds",
    "MIXING_BACKENDS",
    "COHORTS",
]

PyTree = Any
LossFn = Callable[[PyTree, PyTree], jnp.ndarray]  # (params, batch) -> scalar

MIXING_BACKENDS = ("einsum", "fused", "aggregate", "sparse",
                   "sparse_aggregate")
COHORTS = ("vmap", "sequential")


def local_sgd(loss_fn: LossFn, params: PyTree, batches: PyTree,
              eta: jnp.ndarray) -> PyTree:
    """T local SGD iterations (eq. 1). ``batches`` leaves have leading axis T."""
    grad_fn = jax.grad(loss_fn)

    def step(p, batch):
        g = grad_fn(p, batch)
        # keep each leaf at its own dtype (eta is fp32: a bare `x - eta*g`
        # would promote bf16 params) -- matches the mesh train step
        return jax.tree.map(lambda x, gg: (x - eta * gg).astype(x.dtype),
                            p, g), None

    final, _ = jax.lax.scan(step, params, batches)
    return final


def client_deltas(loss_fn: LossFn, global_params: PyTree,
                  client_batches: PyTree, eta: jnp.ndarray) -> PyTree:
    """Per-client scaled cumulative gradients
    ``x_i^{(t,T)} - x^{(t)} = -eta * sum_k grad f_i(x_i^{(t,k)})``.

    ``client_batches`` leaves: (n_clients, T, ...).  Returns leaves with
    leading axis n_clients.
    """
    run = functools.partial(local_sgd, loss_fn)
    with jax.named_scope("local_sgd"):
        finals = jax.vmap(lambda b: run(global_params, b, eta))(
            client_batches)
        return jax.tree.map(lambda f, g: f - g[None], finals, global_params)


def mask_clients(tree: PyTree, active: jnp.ndarray) -> PyTree:
    """Zero dropped clients' rows: each leaf has leading client axis n and
    is multiplied by the (n,) 0/1 ``active`` mask (broadcast over trailing
    dims, cast to the leaf dtype so nothing promotes).  An all-ones mask
    is a bitwise no-op (IEEE ``x * 1.0 == x``)."""
    def one(d):
        shape = (active.shape[0],) + (1,) * (d.ndim - 1)
        return d * active.astype(d.dtype).reshape(shape)

    return jax.tree.map(one, tree)


def mix_deltas(A: jnp.ndarray, deltas: PyTree) -> PyTree:
    """D2D intra-cluster aggregation ``Delta = A @ X_diff`` (eq. 3).

    ``A`` is the (n, n) equal-neighbor matrix (block-diagonal over clusters);
    delta leaves have leading axis n.  Linear in the deltas, so applying it
    leaf-wise over the flattened trailing dims is exact.

    Accumulates in fp32 regardless of delta dtype (bf16 deltas are upcast),
    matching the Pallas kernels' MXU accumulator -- this keeps the einsum
    path a true oracle for the kernel backends.
    """
    def mix(d):
        flat = d.reshape(d.shape[0], -1)
        out = jnp.einsum("ij,jp->ip", A.astype(jnp.float32),
                         flat.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        return out.reshape(d.shape).astype(d.dtype)

    return jax.tree.map(mix, deltas)


def global_update(global_params: PyTree, mixed: PyTree, tau: jnp.ndarray,
                  m: jnp.ndarray) -> PyTree:
    """PS aggregation (eq. 4): ``x + (1/m) sum_i tau_i Delta_i``.

    fp32 accumulation (see ``mix_deltas``); the result is cast back to
    the global-param dtype after the add."""
    def upd(g, d):
        flat = d.reshape(d.shape[0], -1)
        agg = jnp.einsum("i,ip->p", tau.astype(jnp.float32),
                         flat.astype(jnp.float32),
                         preferred_element_type=jnp.float32) / m
        return (g + agg.reshape(g.shape)).astype(g.dtype)

    with jax.named_scope("global_update"):
        return jax.tree.map(upd, global_params, mixed)


def _quantize_deltas(deltas, *, quant, qstate, shards: int = 1):
    """Client-side quantizer step shared by every quant backend: pack the
    delta tree, quantize ``x + residual`` under ``quant``, and advance the
    ``(residuals, key)`` state.  With error feedback off the residual
    buffers stay zero; the PRNG key only advances for stochastic rounding
    (nearest-mode trajectories are key-independent).  ``shards`` forwards
    to ``pack_spec`` (the mesh 'fused_rs' schedule aligns groups to the
    reduce-scatter width)."""
    from repro.fl import packing

    spec = packing.pack_spec(deltas, shards=shards, quant=quant)
    bufs = packing.pack(deltas, spec)
    residuals, key = qstate
    use_key = None
    if quant.rounding == "stochastic":
        key, use_key = jax.random.split(key)
    stored, scales, new_res = packing.quantize_packed(
        bufs, spec, residuals if quant.error_feedback else None, use_key)
    new_qstate = ((new_res if quant.error_feedback else residuals), key)
    return spec, stored, scales, new_qstate


def _mix_and_update(global_params, deltas, A, tau, m, *, backend, chunk,
                    interpret, active, quant, qstate):
    """Eq. 3 + eq. 4 on one backend.  Returns ``(new_global, mixed,
    new_qstate)``; ``mixed`` is None on the aggregate-only backends and
    ``new_qstate`` is ``qstate`` as given when ``quant`` is None.

    With ``quant`` the deltas cross the wire as stored containers +
    per-block scales: the kernel backends consume that wire format
    directly and the einsum oracle dequantizes it first.  The client-side
    quantizer state still advances for dropped clients -- quantization
    happens before the network, the drop on it.

    Straggler masks: the materializing legs zero a dropped client's
    payload rows (its scale rows when quantized) before eq. 3 and remove
    its upload from eq. 4; the aggregate-only legs fold the mask into
    the combine row and never touch the payload.
    """
    from repro.fl import packing
    from repro.kernels.mixing import ops

    if backend == "einsum" and quant is None:
        if active is not None:
            deltas = mask_clients(deltas, active)
            tau = tau * active
        mixed = mix_deltas(A, deltas)
        return global_update(global_params, mixed, tau, m), mixed, qstate

    if quant is None:
        spec = packing.pack_spec(deltas)
        bufs, scales = packing.pack(deltas, spec), None
    else:
        spec, bufs, scales, qstate = _quantize_deltas(
            deltas, quant=quant, qstate=qstate)

    if backend == "einsum":
        # reference oracle: mix the dequantized fp32 buffers with the
        # same mask recipe as the unquantized einsum branch.
        dq = packing.dequantize_packed(bufs, scales, spec)
        if active is not None:
            dq = tuple(mask_clients(list(dq), active))
            tau = tau * active
        A32 = A.astype(jnp.float32)
        tau32 = tau.astype(jnp.float32)
        mixed_bufs = tuple(
            jnp.einsum("ij,jp->ip", A32, b,
                       preferred_element_type=jnp.float32) for b in dq)
        agg_rows = tuple(
            jnp.einsum("i,ip->p", tau32, mb,
                       preferred_element_type=jnp.float32) / m
            for mb in mixed_bufs)
        return (packing.apply_aggregate_row(global_params, agg_rows, spec),
                packing.unpack(mixed_bufs, spec), qstate)

    kw = dict(quant=quant, chunk=chunk, interpret=interpret, active=active)
    if backend in ("aggregate", "sparse_aggregate"):
        agg_rows = ops.aggregate_grouped(A, tau, m, bufs, scales=scales,
                                         **kw)
        return (packing.apply_aggregate_row(global_params, agg_rows, spec),
                None, qstate)
    if active is not None:
        if quant is None:
            bufs = tuple(mask_clients(list(bufs), active))
        else:
            scales = tuple(mask_clients(list(scales), active))
    mixed_bufs, agg_rows = ops.mix_aggregate_grouped(A, tau, m, bufs,
                                                     scales=scales, **kw)
    return (packing.apply_aggregate_row(global_params, agg_rows, spec),
            packing.unpack(mixed_bufs, spec), qstate)


def _check_cohort(cohort: str, mixing_backend: str, quant) -> None:
    if cohort not in COHORTS:
        raise ValueError(f"cohort must be one of {COHORTS}, got {cohort!r}")
    if cohort == "sequential" and (
            quant is not None
            or mixing_backend not in ("aggregate", "sparse_aggregate")):
        raise ValueError(
            "the sequential cohort accumulates the eq.-4 aggregate client "
            "by client and never forms the mixed deltas or a quantized "
            "payload: it takes the 'aggregate' or 'sparse_aggregate' "
            f"backend without quant, got {mixing_backend!r} with "
            f"quant={quant!r}")


def sequential_aggregate(loss_fn: LossFn, global_params: PyTree,
                         client_batches: PyTree, A, tau: jnp.ndarray,
                         m: jnp.ndarray, eta: jnp.ndarray,
                         active: Optional[jnp.ndarray] = None) -> PyTree:
    """The round's aggregate with the clients trained one at a time
    (module docstring, 'sequential'): ``sum_j w_j (x_j - x)``, accumulated
    in fp32 under ``lax.scan`` over the client axis of ``client_batches``."""
    from repro.kernels.mixing import ops

    w = ops.combine_weights(A, tau, m, active)
    f32 = jnp.float32

    def client(acc, xs):
        batches, w_j = xs
        with jax.named_scope("local_sgd"):
            final = local_sgd(loss_fn, global_params, batches, eta)
        with jax.named_scope("mix"):
            return jax.tree.map(
                lambda a, f, g: a + w_j * (f - g).astype(f32),
                acc, final, global_params), None

    acc = jax.tree.map(lambda g: jnp.zeros(g.shape, f32), global_params)
    acc, _ = jax.lax.scan(client, acc, (client_batches, w))
    return acc


def _sequential_round_fn(loss_fn: LossFn, jit: bool):
    """The 'sequential' round: ``sequential_aggregate``, then ``x + acc``
    cast back to each leaf's dtype.  Jitted, these are two programs, and
    the second writes the new params into the buffers of the global
    params it consumes (donated), so that no caller's reference keeps a
    third copy alive into the next round.  The first is the round's
    ``accumulate`` attribute."""
    accumulate = functools.partial(sequential_aggregate, loss_fn)

    def apply(global_params, acc):
        with jax.named_scope("global_update"):
            return jax.tree.map(lambda g, a: (g + a).astype(g.dtype),
                                global_params, acc)

    if jit:
        accumulate = jax.jit(accumulate)
        apply = jax.jit(apply, donate_argnums=0)

    def round_fn(global_params, client_batches, A, tau, m, eta,
                 active=None, qstate=None):
        acc = accumulate(global_params, client_batches, A, tau, m, eta,
                         active)
        return apply(global_params, acc), None

    round_fn.accumulate = accumulate
    return round_fn


def make_round_fn(loss_fn: LossFn, jit: bool = True,
                  mixing_backend: str = "einsum", *, chunk: int = 2048,
                  interpret: Optional[bool] = None, quant=None,
                  cohort: str = "vmap"):
    """Build the jitted global-round function.

    Signature: ``round_fn(global_params, client_batches, A, tau, m, eta[,
    active])``
      - client_batches leaves: (n, T, ...) -- T local minibatches per client
      - A: (n, n) runtime equal-neighbor matrix; the sparse backends take
        the ELL pair ``(idx, w)`` of (n, d_max) arrays instead
        (``repro.core.sparse.SparseA.ell()``)
      - tau: (n,) 0/1 sampling indicators; m = tau.sum() (passed explicitly)
      - active: optional (n,) 0/1 straggler mask; ``m`` must then be the
        effective sampled-and-active count (module docstring)
    Returns ``(new_global_params, mixed_deltas)`` -- the mixed deltas are
    exposed for testing and communication accounting, except under the
    aggregate-only backends ('aggregate', 'sparse_aggregate'), which never
    materialize them and return ``None`` in their place.

    ``mixing_backend`` selects the eq. 3 + eq. 4 implementation (module
    docstring); ``chunk``/``interpret`` configure the Pallas backends and
    are ignored by 'einsum'.  ``interpret=None`` (default) resolves per
    platform -- compiled on TPU, interpreter elsewhere
    (``repro.kernels.dispatch.default_interpret``).

    ``quant`` (a ``repro.fl.packing.QuantSpec``, default None) switches
    the round to quantized payload groups: the signature grows a trailing
    ``qstate`` argument (``packing.init_quant_state``) and the round
    returns ``(new_global_params, mixed_deltas, new_qstate)``; ``chunk``
    must then be a multiple of ``quant.block``.  With ``quant=None``
    nothing about the unquantized path changes.

    ``cohort='sequential'`` trains the clients one at a time (module
    docstring); it takes the aggregate-only backends without ``quant``,
    returns ``(new_global_params, None)`` and, jitted, consumes the
    global params it is given (``_sequential_round_fn``).
    """
    if mixing_backend not in MIXING_BACKENDS:
        raise ValueError(
            f"mixing_backend must be one of {MIXING_BACKENDS}, "
            f"got {mixing_backend!r}")
    _check_cohort(cohort, mixing_backend, quant)
    if quant is not None:
        from repro.kernels.mixing.ops import _check_quant_chunk
        _check_quant_chunk(quant, chunk)

    if cohort == "sequential":
        return _sequential_round_fn(loss_fn, jit)

    def round_fn(global_params: PyTree, client_batches: PyTree,
                 A: jnp.ndarray, tau: jnp.ndarray, m: jnp.ndarray,
                 eta: jnp.ndarray, active: Optional[jnp.ndarray] = None,
                 qstate=None):
        if quant is not None and qstate is None:
            raise ValueError(
                "quantized round_fn needs the quantizer state: build "
                "it with packing.init_quant_state(spec, n) and thread "
                "the returned new_qstate into the next round")
        deltas = client_deltas(loss_fn, global_params, client_batches, eta)
        with jax.named_scope("mix"):
            new_global, mixed, qstate = _mix_and_update(
                global_params, deltas, A, tau, m, backend=mixing_backend,
                chunk=chunk, interpret=interpret, active=active,
                quant=quant, qstate=qstate)
        if quant is None:
            return new_global, mixed
        return new_global, mixed, qstate

    return jax.jit(round_fn) if jit else round_fn


def make_scanned_rounds(loss_fn: LossFn, K: int, jit: bool = True,
                        mixing_backend: str = "einsum", *,
                        chunk: int = 2048,
                        interpret: Optional[bool] = None, quant=None,
                        cohort: str = "vmap"):
    """Build a driver that runs ``K`` global rounds in one ``lax.scan``.

    The host builds the whole time-varying topology sequence up front and
    dispatches to the device once per K rounds instead of once per round:

    ``scanned(global_params, client_batches_seq, A_seq, tau_seq, m_seq,
    eta_seq[, active_seq]) -> (final_params, params_seq)``

      - client_batches_seq leaves: (K, n, T, ...) -- stacked round batches
      - A_seq (K, n, n), tau_seq (K, n), m_seq (K,), eta_seq (K,); sparse
        backends take ``A_seq = (idx_seq, w_seq)`` of (K, n, d_max) arrays
        (``SparseAseq.ell()``, shared d_max so the scan keeps one compiled
        shape) -- ``lax.scan`` slices the tuple leaves per round
      - active_seq: optional (K, n) stacked straggler masks (the
        ``RoundPlan`` ``active_t`` column)
      - params_seq leaves: (K, ...) -- the global params after each round
        (params_seq[K-1] == final_params), so per-round evaluation and
        ``History`` bookkeeping stay exact.

    The scan body is the *same* composition as ``make_round_fn``'s body,
    so the trajectory is bitwise-identical to K sequential ``round_fn``
    calls on the same inputs (asserted in tests/test_fused_mixing.py).

    With ``quant`` set the quantizer state joins the scan carry: the
    driver takes a trailing ``qstate`` argument and returns ``(final,
    params_seq, final_qstate)`` -- error-feedback residuals accumulate
    across the K rounds exactly as in the sequential loop.
    """
    round_fn = make_round_fn(loss_fn, jit=False,
                             mixing_backend=mixing_backend, chunk=chunk,
                             interpret=interpret, quant=quant, cohort=cohort)

    def scanned(global_params: PyTree, client_batches_seq: PyTree,
                A_seq: jnp.ndarray, tau_seq: jnp.ndarray,
                m_seq: jnp.ndarray, eta_seq: jnp.ndarray,
                active_seq: Optional[jnp.ndarray] = None, qstate=None):
        def body(carry, xs):
            params, qs = carry
            batches, A, tau, m, eta = xs[:5]
            active = xs[5] if active_seq is not None else None
            out = round_fn(params, batches, A, tau, m, eta, active, qs)
            new_qs = out[2] if quant is not None else qs
            return (out[0], new_qs), out[0]

        xs = (client_batches_seq, A_seq, tau_seq, m_seq, eta_seq)
        if active_seq is not None:
            xs = xs + (active_seq,)
        (final, final_qstate), params_seq = jax.lax.scan(
            body, (global_params, qstate), xs, length=K)
        if quant is None:
            return final, params_seq
        return final, params_seq, final_qstate

    return jax.jit(scanned) if jit else scanned
