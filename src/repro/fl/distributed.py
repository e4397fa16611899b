"""Mesh-distributed semi-decentralized FL round (Algorithm 1) + sharded
inference steps.

Mapping (DESIGN §2): client i = one (pod, data) mesh index; D2D cluster =
one pod (ICI domain); the equal-neighbor matrix ``A`` (block-diagonal over
pods) and the sampling mask ``tau`` are *runtime inputs*, so one compiled
step serves every round of Algorithm 1, FedAvg (A=I) and COLREL (fixed m).

``train_step`` phases:
  1. broadcast  -- global params -> per-client stacked params (leading
     client axis sharded over (pod, data); model dims over 'model').
  2. local SGD  -- ``lax.scan`` of T steps per client under ``jax.vmap``;
     tensor parallelism inside each client is delegated to GSPMD via the
     parameter shardings.
  3. D2D mixing -- ``Delta = A @ X_diff`` over the client axis.  Three
     interchangeable schedules (see ``mixing=``):
       'ring'   -- intra-pod ``ppermute`` ring streaming neighbor deltas
                   while accumulating ``a_ij X_j``: O(1) extra memory,
                   n_data permute hops on cheap ICI.  TPU-native D2D.
       'gather' -- ``all_gather`` the client axis then weighted-sum
                   (O(n) memory blowup; the naive schedule).
       'einsum' -- jit-level dense matmul over the stacked client axis
                   (XLA chooses the schedule; paper eq. (3) verbatim).
       'fused'  -- jit-level one-pass sibling of 'einsum': packs the
                   delta pytree into per-dtype lane-aligned (n, P_g)
                   buffers (``repro.fl.packing``; no result_type
                   promotion on the wire) and applies the algebraic
                   identity ``sum_i tau_i (A X)_i = (tau^T A) X`` so the
                   payload is read ONCE and the mixed deltas are never
                   materialized (the train step only returns the new
                   global params).  GSPMD shards the packed matmuls.
       'fused_rs' -- manual shard_map version of 'fused': each worker
                   scales its OWN packed rows by its precombined D2S
                   weight ``w_i = ((tau^T A)/m)_i`` and each group's
                   (P_g,) aggregate row is REDUCE-SCATTERED over 'data'
                   (ZeRO-style) + psum-ed over 'pod', so every worker
                   receives only P_g/n_data columns instead of the full
                   row a psum would deliver (2x less cross-worker
                   traffic than the per-leaf psum schedule; see
                   ``benchmarks.mixing_kernel.mesh_traffic_model``).
                   Mixed deltas are never materialized and no (n, n)
                   matmul runs on-device -- only an elementwise scale.
  4. D2S        -- ``psum`` of ``tau_i * Delta_i`` over (pod, data) --
     the expensive cross-pod collective -- scaled by 1/m (paper eq. (4)).

Backend-selection matrix (mixing x runtime x scan)::

    mixing     collectives        mixed deltas   K-round scan   best when
    --------   ----------------   ------------   ------------   ------------------
    ring       ppermute + psum    materialized   yes (*)        TPU ICI, ZeRO
                                                                 (zero=True)
    gather     all_gather + psum  materialized   yes (*)        debugging only
    einsum     GSPMD-scheduled    materialized   yes            oracle parity
    fused      GSPMD-scheduled    never          yes            payload read once
    fused_rs   psum_scatter(+psum) never         yes (*)        min cross-worker
                                                                 bytes per round

    (*) manual-collective schedules run under ``jax.shard_map``.

Scan: ``make_scanned_train_steps(cfg, mesh, K, ...)`` lifts the stacked
``(A_t, tau_t, m_t, eta_t[, active_t])`` ``lax.scan`` of ``core.rounds
.make_scanned_rounds`` into the mesh runtime, so a K-round time-varying
topology trajectory compiles and dispatches ONCE for every mixing
schedule above (single-host oracle: ``repro.core.rounds``).

Drivers normally do not call these factories directly: a ``RoundPlan``
(``repro.fl.plan``) holds the trajectory and ``ExecutionConfig(mesh=,
model_cfg=, backend=<schedule above>, scan=)`` selects this runtime via
``repro.fl.engine.MeshEngine`` -- including the per-round ``active_t``
straggler masks, which ``_mix_and_aggregate`` folds into the combine
row (one-pass schedules) or the delta rows (materializing schedules).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.adjacency import network_matrix
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.models import sharding as shard_rules
from repro.launch.mesh import (client_axes, data_axis_size,
                               model_axis_size, n_clients_of)

PyTree = Any

__all__ = ["make_train_step", "make_scanned_train_steps",
           "make_prefill_step", "make_decode_step",
           "build_topology_inputs", "MIXINGS"]

MIXINGS = ("ring", "gather", "einsum", "fused", "fused_rs")


def _shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` without replication checks; ``axis_names``
    restricts manualness to those axes (partial shard_map)."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def _gathered(mesh, rows):
    """All-gather reduce-scattered aggregate rows once, behind an
    optimization barrier, before they are unpacked into leaves.  Without
    the barrier the TPU compiler folds the unpack's per-leaf slices into
    the collective, and its compile time grows with the payload: for a
    v5e 2x2 mesh, a 617M-parameter stablelm-1.6b cut took about 14
    minutes to compile without it and 12 s with it."""
    return jax.lax.optimization_barrier(
        jax.lax.with_sharding_constraint(rows, NamedSharding(mesh, P())))


def _shardings(mesh, specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def zero_specs(specs: PyTree, params: PyTree, data_size: int) -> PyTree:
    """ZeRO-style global-parameter sharding: additionally shard dim 0 over
    'data' wherever it is unsharded and divisible.  The global copy then
    occupies 1/data_size of HBM per chip; the per-client broadcast
    all-gathers it once per round and the D2S aggregation reduce-scatters
    back (see ``_mix_and_aggregate``)."""

    def one(spec, leaf):
        t = tuple(spec)
        # first unsharded, divisible dim (scanned stacks have a leading
        # layer axis that rarely divides the data axis -- skip past it)
        for i, s in enumerate(t):
            if s is None and leaf.shape[i] % data_size == 0 \
                    and leaf.shape[i] >= data_size:
                return P(*(t[:i] + ("data",) + t[i + 1:]))
        return spec

    return jax.tree.map(one, specs, params,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# D2D mixing + D2S aggregation (shard_map over the mesh)
# ---------------------------------------------------------------------------

def _mix_and_aggregate(mesh, mixing: str, deltas: PyTree, A: jnp.ndarray,
                       tau: jnp.ndarray, m: jnp.ndarray,
                       global_params: PyTree, msize: int,
                       zero: bool = False,
                       active: Optional[jnp.ndarray] = None,
                       quant=None, qstate=None) -> PyTree:
    """new_global = global + (1/m) sum_i tau_i (A @ deltas)_i.

    All client-axis communication happens here: the D2D mixing over the
    intra-pod 'data' axis and the D2S psum over (pod, data).

    ``active`` is the optional (n,) 0/1 straggler mask (``RoundPlan``
    ``active_t``): dropped clients contribute zero delta and never
    upload; ``m`` must then be the effective sampled-and-active count.
    The one-pass schedules ('fused'/'fused_rs') fold the mask into the
    precombined weight row (``combine_weights``) -- zero payload cost;
    the materializing schedules zero the dropped rows before eq. 3.  An
    all-ones mask is bitwise-identical to ``active=None``.

    ``quant`` (a ``repro.fl.packing.QuantSpec``) switches the one-pass
    schedules to quantized payload groups: the deltas are quantized
    client-side (error feedback in ``qstate``) and only the stored
    containers + per-block scales cross the client axis; the return value
    becomes ``(new_global, new_qstate)``.  Only 'fused' and 'fused_rs'
    support it -- the materializing schedules would decompress n times.
    """
    caxes = client_axes(mesh)
    n_data = data_axis_size(mesh)
    n = n_clients_of(mesh)

    if quant is not None and mixing not in ("fused", "fused_rs"):
        raise ValueError(
            "quantized payloads on the mesh runtime require the one-pass "
            f"'fused' or 'fused_rs' schedules, got {mixing!r}")

    if active is not None and mixing in ("ring", "gather", "einsum"):
        act = active.astype(jnp.float32)
        deltas = jax.tree.map(
            lambda d: d * act.astype(d.dtype).reshape(
                (n,) + (1,) * (d.ndim - 1)),
            deltas)
        tau = tau * act

    if mixing == "einsum":
        # paper eq. (3) verbatim at the jit level; XLA picks the schedule.
        # fp32 accumulation matches the single-host oracle and the
        # Pallas kernels (repro.core.rounds docstring).
        def mix(d):
            flat = d.reshape(n, -1)
            out = jnp.einsum("ij,jp->ip", A.astype(jnp.float32),
                             flat.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
            return out.reshape(d.shape).astype(d.dtype)

        mixed = jax.tree.map(mix, deltas)

        def upd(g, d):
            flat = d.reshape(n, -1)
            agg = jnp.einsum("i,ip->p", tau.astype(jnp.float32),
                             flat.astype(jnp.float32),
                             preferred_element_type=jnp.float32) / m
            return (g + agg.reshape(g.shape)).astype(g.dtype)

        return jax.tree.map(upd, global_params, mixed)

    if mixing == "fused":
        # one-pass sibling of 'einsum': sum_i tau_i (A X)_i = (tau^T A) X.
        # Each dtype group's packed buffer is read once at its native
        # width (no result_type promotion on the wire) and the (n, P)
        # mixed intermediate is never formed -- the train step only needs
        # the new global.
        from repro.fl import packing
        from repro.kernels.mixing.ops import combine_weights

        w = combine_weights(A, tau, m, active)
        if quant is not None:
            from repro.core.rounds import _quantize_deltas

            spec, stored, scales, new_qstate = _quantize_deltas(
                deltas, quant=quant, qstate=qstate)
            # the wire carries (stored, scales); the aggregate row is the
            # combine-row product over the dequantized fp32 values
            dq = packing.dequantize_packed(stored, scales, spec)
            agg_rows = tuple(
                jnp.einsum("j,jp->p", w, b,
                           preferred_element_type=jnp.float32)
                for b in dq)
            return (packing.apply_aggregate_row(global_params, agg_rows,
                                                spec), new_qstate)
        spec = packing.pack_spec(deltas)
        bufs = packing.pack(deltas, spec)           # per-group (n, P_pad_g)
        agg_rows = tuple(
            jnp.einsum("j,jp->p", w, b.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
            for b in bufs)
        return packing.apply_aggregate_row(global_params, agg_rows, spec)

    if mixing == "fused_rs":
        # manual worker-sharded 'fused': worker i holds packed row X_i
        # (client axis sharded over (pod, data)) and its own weight
        # w_i = ((tau^T A)/m)_i, computes the local contribution w_i X_i,
        # and the aggregate row sum_i w_i X_i is reduce-scattered over
        # 'data' (each worker receives only its P_pad/n_data column
        # shard, ZeRO-style) then psum-ed over 'pod'.  No mixed deltas,
        # no (n, n) matmul, and half the cross-worker bytes of a psum.
        from repro.fl import packing
        from repro.kernels.mixing.ops import combine_weights

        w = combine_weights(A, tau, m, active)             # (n,) fp32
        if quant is not None:
            from repro.core.rounds import _quantize_deltas

            # groups align to lcm(lane * n_data, block) so both the
            # reduce-scatter and the scale blocks tile evenly
            spec, stored, scales, new_qstate = _quantize_deltas(
                deltas, quant=quant, qstate=qstate, shards=n_data)

            def rs_q_body(bs, ss, wv):
                # worker i dequantizes only its OWN packed row -- the
                # cross-worker traffic is the psum_scatter of the fp32
                # contribution, while the stored+scales rows stay local
                outs = []
                for b, s in zip(bs, ss):
                    dq = packing.dequantize_group(b, s, quant)
                    contrib = wv[0] * dq[0]                 # (P_pad_g,)
                    part = jax.lax.psum_scatter(contrib, caxes[-1],
                                                scatter_dimension=0,
                                                tiled=True)
                    if len(caxes) > 1:
                        part = jax.lax.psum(part, caxes[:-1])
                    outs.append(part)
                return tuple(outs)

            agg_rows = _shard_map(
                rs_q_body, mesh,
                in_specs=(tuple(P(caxes, None) for _ in stored),
                          tuple(P(caxes, None) for _ in scales),
                          P(caxes)),
                out_specs=tuple(P(caxes[-1]) for _ in stored))(
                    stored, scales, w)
            return (packing.apply_aggregate_row(
                global_params, _gathered(mesh, agg_rows), spec), new_qstate)

        # every group's P_pad_g is shard-aligned, so each per-dtype row
        # reduce-scatters evenly over 'data' on its own
        spec = packing.pack_spec(deltas, shards=n_data)
        bufs = packing.pack(deltas, spec)           # per-group (n, P_pad_g)

        def rs_body(bs, wv):
            outs = []
            for b in bs:
                contrib = wv[0] * b[0].astype(jnp.float32)  # (P_pad_g,)
                part = jax.lax.psum_scatter(contrib, caxes[-1],
                                            scatter_dimension=0, tiled=True)
                if len(caxes) > 1:
                    part = jax.lax.psum(part, caxes[:-1])
                outs.append(part)
            return tuple(outs)

        agg_rows = _shard_map(
            rs_body, mesh,
            in_specs=(tuple(P(caxes, None) for _ in bufs), P(caxes)),
            out_specs=tuple(P(caxes[-1]) for _ in bufs))(bufs, w)
        return packing.apply_aggregate_row(
            global_params, _gathered(mesh, agg_rows), spec)

    gspecs = shard_rules.param_specs(global_params, msize)
    if zero:
        gspecs = zero_specs(gspecs, global_params, n_data)
    dspecs = shard_rules.param_specs(global_params, msize, prefix=(caxes,))
    def _zero_dim(s):
        t = tuple(s)
        return t.index("data") if "data" in t else -1

    zero_dims = jax.tree.map(_zero_dim, gspecs,
                             is_leaf=lambda x: isinstance(x, P))

    def body(deltas, A, tau, m, global_params):
        d_i = jax.lax.axis_index(caxes[-1])
        p_i = jax.lax.axis_index(caxes[0]) if len(caxes) > 1 else 0
        my = p_i * n_data + d_i
        tau_my = jax.lax.dynamic_index_in_dim(tau, my, keepdims=False)

        def a_of(j):
            row = jax.lax.dynamic_index_in_dim(A, my, keepdims=False)
            return jax.lax.dynamic_index_in_dim(row, j, keepdims=False)

        if mixing == "ring":
            perm = [(i, (i + 1) % n_data) for i in range(n_data)]

            def step(r, carry):
                recv, acc = carry
                j = p_i * n_data + (d_i - r) % n_data
                a = a_of(j)
                acc = jax.tree.map(
                    lambda ac, rv: ac + a.astype(rv.dtype) * rv, acc, recv)
                recv = jax.tree.map(
                    lambda rv: jax.lax.ppermute(rv, caxes[-1], perm), recv)
                return recv, acc

            zeros = jax.tree.map(jnp.zeros_like, deltas)
            _, mixed = jax.lax.fori_loop(0, n_data, step, (deltas, zeros))
        else:  # 'gather'
            def mix_leaf(d):
                g = jax.lax.all_gather(d, caxes, axis=0, tiled=True)
                row_start = p_i * n_data
                arow = jax.lax.dynamic_slice_in_dim(
                    jax.lax.dynamic_index_in_dim(A, my, keepdims=False),
                    row_start, n_data)
                gpod = jax.lax.dynamic_slice_in_dim(g, row_start, n_data)
                flat = gpod.reshape(n_data, -1)
                out = (arow.astype(flat.dtype) @ flat).reshape(d.shape[1:])
                return out[None]

            mixed = jax.tree.map(mix_leaf, deltas)

        # D2S: sum_i tau_i Delta_i over every client -- cross-pod collective
        def agg_leaf(gl, mx, zd):
            contrib = tau_my.astype(mx.dtype) * mx[0]
            if zd >= 0:
                # ZeRO: reduce-scatter over 'data' so each chip only
                # receives (and stores) its own global-param shard.
                part = jax.lax.psum_scatter(contrib, caxes[-1],
                                            scatter_dimension=zd,
                                            tiled=True)
                if len(caxes) > 1:
                    part = jax.lax.psum(part, caxes[:-1])
                return (gl + part.astype(jnp.float32) / m).astype(gl.dtype)
            total = jax.lax.psum(contrib, caxes)
            return (gl + total.astype(jnp.float32) / m).astype(gl.dtype)

        return jax.tree.map(agg_leaf, global_params, mixed, zero_dims)

    return _shard_map(
        body, mesh,
        in_specs=(dspecs, P(None, None), P(None), P(), gspecs),
        out_specs=gspecs,
    )(deltas, A, tau, m, global_params)


# ---------------------------------------------------------------------------
# train step (Algorithm 1, one global round)
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, mesh, mixing: str = "ring",
                    jit: bool = True, zero: bool = False,
                    client_impl: str = "vmap", quant=None):
    """Build ``train_step(global_params, tokens, A, tau, m, eta[, prefix]
    [, active])``.

    tokens: (n_clients, T, B_local, S+1) int32 -- per-client, per-local-step
    minibatches; inputs/targets are adjacent slices.  prefix (audio/vlm):
    (n_clients, T, B_local, P, fdim).  active: optional (n,) 0/1
    straggler mask (see ``_mix_and_aggregate``).  Returns the new global
    params (same sharding as the input -- rounds compose).

    ``client_impl``:
      'vmap'      -- batch the client axis; GSPMD partitions it (default).
      'shardmap'  -- partial shard_map over the client axes only ('model'
                     stays automatic).  Functionally identical; required
                     for nesting manual 'model'-axis collectives inside the
                     per-client step (SP-MLP / expert-parallel MoE), which
                     vmap's replication rule cannot express (EXPERIMENTS
                     §Perf pair A iter 6b).

    ``quant`` (a ``repro.fl.packing.QuantSpec``; 'fused'/'fused_rs' only)
    quantizes the payload client-side: the step grows a trailing
    ``qstate`` argument and returns ``(new_global, new_qstate)``
    (``_mix_and_aggregate``).
    """
    if mixing not in MIXINGS:
        raise ValueError(f"mixing must be one of {MIXINGS}")
    if zero and mixing != "ring":
        raise ValueError("zero sharding is implemented for ring mixing")
    if client_impl not in ("vmap", "shardmap"):
        raise ValueError("client_impl must be 'vmap' or 'shardmap'")
    if quant is not None and mixing not in ("fused", "fused_rs"):
        raise ValueError(
            "quantized payloads on the mesh runtime require the one-pass "
            f"'fused' or 'fused_rs' schedules, got {mixing!r}")
    model = Model(cfg)
    n = n_clients_of(mesh)
    caxes = client_axes(mesh)
    msize = model_axis_size(mesh)

    def train_step(global_params, tokens, A, tau, m, eta, prefix=None,
                   active=None, qstate=None):
        cspecs = shard_rules.param_specs(global_params, msize,
                                         prefix=(caxes,))
        cshard = _shardings(mesh, cspecs)

        # 1. broadcast global -> per-client stacked params
        per_client = jax.tree.map(
            lambda g: jnp.broadcast_to(g[None], (n,) + g.shape),
            global_params)
        per_client = jax.lax.with_sharding_constraint(per_client, cshard)

        # 2. T local SGD steps per client (paper eq. (1))
        def one_client(p0, toks, pe):
            def step(p, xs):
                if pe is None:
                    tk = xs
                    batch = (tk[..., :-1], tk[..., 1:])
                else:
                    tk, pex = xs
                    batch = (tk[..., :-1], tk[..., 1:], pex)
                g = jax.grad(model.loss)(p, batch)
                return jax.tree.map(lambda a, b: (a - eta * b).astype(a.dtype),
                                    p, g), None

            xs = toks if pe is None else (toks, pe)
            pT, _ = jax.lax.scan(step, p0, xs)
            return pT

        if client_impl == "vmap":
            finals = jax.vmap(one_client)(
                per_client, tokens,
                prefix if prefix is not None else None) \
                if prefix is not None else jax.vmap(
                    lambda p0, t: one_client(p0, t, None))(per_client,
                                                           tokens)
        else:
            # partial shard_map: client axes manual (each shard sees ONE
            # client, squeezed), 'model' axis stays automatic so nested
            # manual collectives (SP-MLP, EP-MoE) can claim it.
            sq = lambda t: jax.tree.map(lambda a: a[0], t)       # noqa: E731
            ex = lambda t: jax.tree.map(lambda a: a[None], t)    # noqa: E731
            cax_spec = P(caxes)

            def spec_of(tree, extra):
                return jax.tree.map(
                    lambda _: P(*((caxes,) + (None,) * extra)), tree)

            if prefix is None:
                body = lambda p0, t: ex(                         # noqa: E731
                    one_client(sq(p0), sq(t), None))
                in_specs = (
                    jax.tree.map(lambda a: P(*((caxes,)
                                               + (None,) * (a.ndim - 1))),
                                 per_client),
                    P(caxes, None, None, None))
                finals = _shard_map(
                    body, mesh, in_specs=in_specs,
                    out_specs=in_specs[0],
                    axis_names=set(caxes))(per_client, tokens)
            else:
                body = lambda p0, t, pe: ex(                     # noqa: E731
                    one_client(sq(p0), sq(t), sq(pe)))
                pspec = jax.tree.map(
                    lambda a: P(*((caxes,) + (None,) * (a.ndim - 1))),
                    per_client)
                finals = _shard_map(
                    body, mesh,
                    in_specs=(pspec, P(caxes, None, None, None),
                              P(caxes, None, None, None, None)),
                    out_specs=pspec,
                    axis_names=set(caxes))(per_client, tokens, prefix)
        finals = jax.lax.with_sharding_constraint(finals, cshard)

        # scaled cumulative gradients x_i^{(t,T)} - x^{(t)}
        deltas = jax.tree.map(lambda f, g: f - g[None], finals,
                              global_params)

        # 3.+4. D2D mixing + D2S sampled aggregation
        if quant is not None and qstate is None:
            raise ValueError(
                "quantized train_step needs the quantizer state: build it "
                "with packing.init_quant_state(spec, n) and thread the "
                "returned new_qstate into the next step")
        return _mix_and_aggregate(mesh, mixing, deltas, A, tau, m,
                                  global_params, msize, zero=zero,
                                  active=active, quant=quant,
                                  qstate=qstate)

    if not jit:
        return train_step
    return jax.jit(train_step)


# ---------------------------------------------------------------------------
# scanned multi-round driver (one dispatch per K-round trajectory)
# ---------------------------------------------------------------------------

def make_scanned_train_steps(cfg: ModelConfig, mesh, K: int,
                             mixing: str = "ring", jit: bool = True,
                             zero: bool = False,
                             client_impl: str = "vmap", quant=None):
    """Build a driver that runs ``K`` mesh train steps in one ``lax.scan``.

    The mesh sibling of ``repro.core.rounds.make_scanned_rounds``: the host
    stacks the whole time-varying topology trajectory up front and the
    K-round program compiles and dispatches to the mesh ONCE:

    ``scanned(global_params, tokens_seq, A_seq, tau_seq, m_seq, eta_seq[,
    prefix_seq][, active_seq]) -> (final_params, params_seq)``

      - tokens_seq: (K, n_clients, T, B_local, S+1) stacked round batches
        (prefix_seq, when given: (K, n_clients, T, B_local, P, fdim))
      - A_seq (K, n, n), tau_seq (K, n), m_seq (K,), eta_seq (K,)
      - active_seq: optional (K, n) stacked straggler masks (the
        ``RoundPlan`` ``active_t`` column)
      - params_seq leaves: (K, ...) -- global params after each round
        (``params_seq[K-1] == final_params``), so per-round evaluation and
        ``History`` bookkeeping stay exact.

    The scan body is the *same* train step ``make_train_step`` builds (any
    ``mixing`` schedule, including the manual shard_map ones -- shard_map
    nests under scan), so the trajectory is bitwise-identical to K
    sequential ``train_step`` dispatches on the same inputs (asserted in
    tests/test_mesh_scan_equivalence.py).

    With ``quant`` set the quantizer state joins the scan carry: the
    driver takes a trailing ``qstate`` argument and returns
    ``(final_params, params_seq, final_qstate)``."""
    step = make_train_step(cfg, mesh, mixing=mixing, jit=False, zero=zero,
                           client_impl=client_impl, quant=quant)

    if quant is not None:
        def scanned_q(global_params, tokens_seq, A_seq, tau_seq, m_seq,
                      eta_seq, prefix_seq=None, active_seq=None,
                      qstate=None):
            def body(carry, xs):
                params, qs = carry
                tokens, A, tau, m, eta = xs[:5]
                rest = list(xs[5:])
                prefix = rest.pop(0) if prefix_seq is not None else None
                active = rest.pop(0) if active_seq is not None else None
                new, new_qs = step(params, tokens, A, tau, m, eta,
                                   prefix=prefix, active=active,
                                   qstate=qs)
                return (new, new_qs), new

            xs = (tokens_seq, A_seq, tau_seq, m_seq, eta_seq)
            if prefix_seq is not None:
                xs = xs + (prefix_seq,)
            if active_seq is not None:
                xs = xs + (active_seq,)
            (final, final_qstate), params_seq = jax.lax.scan(
                body, (global_params, qstate), xs, length=K)
            return final, params_seq, final_qstate

        return jax.jit(scanned_q) if jit else scanned_q

    def scanned(global_params, tokens_seq, A_seq, tau_seq, m_seq, eta_seq,
                prefix_seq=None, active_seq=None):
        def body(params, xs):
            tokens, A, tau, m, eta = xs[:5]
            rest = list(xs[5:])
            prefix = rest.pop(0) if prefix_seq is not None else None
            active = rest.pop(0) if active_seq is not None else None
            new = step(params, tokens, A, tau, m, eta, prefix=prefix,
                       active=active)
            return new, new

        xs = (tokens_seq, A_seq, tau_seq, m_seq, eta_seq)
        if prefix_seq is not None:
            xs = xs + (prefix_seq,)
        if active_seq is not None:
            xs = xs + (active_seq,)
        final, params_seq = jax.lax.scan(body, global_params, xs, length=K)
        return final, params_seq

    return jax.jit(scanned) if jit else scanned


# ---------------------------------------------------------------------------
# inference steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, mesh, batch_axes, cache_len: int,
                      jit: bool = True):
    """``prefill_step(params, tokens[, prefix]) -> (logits, cache)``."""
    model = Model(cfg)
    msize = model_axis_size(mesh)

    def prefill_step(params, tokens, prefix=None):
        logits, cache = model.prefill(params, tokens, prefix,
                                      max_len=cache_len)
        cspecs = shard_rules.cache_specs(cache, batch_axes, msize)
        cache = jax.lax.with_sharding_constraint(
            cache, _shardings(mesh, cspecs))
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, P(batch_axes, None)))
        return logits, cache

    return jax.jit(prefill_step) if jit else prefill_step


def make_decode_step(cfg: ModelConfig, mesh, batch_axes, jit: bool = True,
                     donate_cache: bool = True):
    """``decode_step(params, cache, token, pos) -> (logits, cache)``.

    The cache is donated by default (it is consumed every step): the new
    cache aliases the old buffer, removing a full cache copy from both the
    output and temp footprints -- decode is the memory-bound shape, so
    this is the difference between fitting HBM or not for the 32k-deep
    caches (EXPERIMENTS §Perf, decode note)."""
    model = Model(cfg)
    msize = model_axis_size(mesh)

    def decode_step(params, cache, token, pos):
        logits, new_cache = model.decode(params, cache, token, pos)
        cspecs = shard_rules.cache_specs(new_cache, batch_axes, msize)
        new_cache = jax.lax.with_sharding_constraint(
            new_cache, _shardings(mesh, cspecs))
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, P(batch_axes, None)))
        return logits, new_cache

    if not jit:
        return decode_step
    kw = dict(donate_argnums=(1,)) if donate_cache else {}
    return jax.jit(decode_step, **kw)


# ---------------------------------------------------------------------------
# topology inputs for the mesh round (host-side, paper Sec. 3.3)
# ---------------------------------------------------------------------------

def build_topology_inputs(network, rng: np.random.Generator,
                          tau_idx: Optional[np.ndarray] = None,
                          t: int = 0) -> Tuple[np.ndarray, Any]:
    """Sample G(t) and return (A, clusters) ready to feed the mesh step.
    Client ordering must match the mesh flattening (pod-major).

    ``network`` is any ``repro.topology`` model (or the deprecated
    ``D2DNetwork`` shim); pass the round index ``t`` so time-correlated
    families (geometric mobility, periodic re-clustering) advance
    instead of resetting -- stateful models require consecutive
    ``t = 0, 1, 2, ...``."""
    from .plan import _sample_snapshot
    clusters = _sample_snapshot(network, rng, t)
    A = network_matrix(clusters, network.n)
    return A.astype(np.float32), clusters
