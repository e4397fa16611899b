"""Runtime engines: execute a ``RoundPlan`` and return a ``History``.

This module is the ONLY place that knows how an abstract execution
request (``ExecutionConfig``) maps onto a compiled runtime: the
backend-selection matrix that used to be smeared across ``FederatedServer``
kwargs lives in ``resolve_backend`` and nowhere else.

    ExecutionConfig   what to run: backend name, scan on/off, mixed-delta
                      recording, kernel knobs (chunk/interpret), jit, and
                      -- for the mesh runtime -- the mesh + model config.
    Engine            the protocol: ``execute(plan, params, batches, ...)
                      -> (final_params, History)``.
    LocalEngine       single-host runtime over ``repro.core.rounds``
                      (``make_round_fn`` / ``make_scanned_rounds``).
    MeshEngine        mesh runtime over ``repro.fl.distributed``
                      (``make_train_step`` / ``make_scanned_train_steps``).
    StreamEngine      event-driven semi-async runtime
                      (``repro.fl.stream``), selected by
                      ``ExecutionConfig(stream=StreamConfig(...))``.
    make_engine       ExecutionConfig -> the right engine.

Backend selection (one matrix, one place)::

    runtime      backends                       record_mixed     scan
    -----------  -----------------------------  ---------------  ----
    LocalEngine  einsum | fused | aggregate     False upgrades    yes
                 | sparse | sparse_aggregate    fused ->
                                                'aggregate' and
                                                sparse ->
                                                'sparse_aggregate'
    MeshEngine   ring | gather | einsum         unsupported       yes
                 | fused | fused_rs
    StreamEngine einsum | fused | aggregate     unsupported       no
                 (fused always -> 'aggregate';  (mixed deltas     (event
                 sparse* rejected)              never kept)       loop)

Every local backend takes a quantized payload; on the mesh only the
one-pass 'fused' / 'fused_rs' schedules do, and the stream runtime takes
none.

Cohort form (LocalEngine): the round trains all n clients under
``jax.vmap`` where the ``(1 + 2n)`` param copies that keeps live fit the
device's memory (``memory_stats()["bytes_limit"]``; a device that reports
none counts as roomy), and one client at a time under ``lax.scan``
otherwise (``repro.core.rounds``, 'sequential'), which takes the
aggregate-only backends without quant.  Each ``execute`` counts its form
as ``engine.cohort`` ``vmap`` / ``sequential``.

The sparse backends consume the plan's ``A_t`` column in ELL form
(``repro.core.sparse``) -- a sparse plan never densifies on this path,
which is what lets ``n`` scale past the dense O(n^2) wall.

Straggler masks: when ``plan.has_dropout`` the per-round ``active_t``
column is threaded into the round functions (inactive clients contribute
zero delta and are renormalized out of the ``(tau^T A)/m`` combine row);
all-ones plans skip the mask plumbing entirely, so full participation is
bitwise-identical to the pre-plan runtime.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import CommLedger
from repro.core.rounds import MIXING_BACKENDS, make_round_fn, \
    make_scanned_rounds
from repro.core.server import History, RoundRecord
from repro.core.sparse import SparseAseq
from repro.spans import count, span
from .distributed import MIXINGS, make_scanned_train_steps, make_train_step
from .plan import RoundPlan

__all__ = ["ExecutionConfig", "Engine", "LocalEngine", "MeshEngine",
           "cohort_form", "make_engine", "resolve_backend"]

PyTree = Any
EvalFn = Callable[[PyTree], Dict[str, float]]


@dataclasses.dataclass(frozen=True)
class ExecutionConfig:
    """How to execute a plan -- the single runtime-selection object.

    ``backend`` names a single-host mixing backend (``MIXING_BACKENDS``)
    or, when ``mesh`` is set, a mesh mixing schedule (``MIXINGS``).
    ``scan=True`` compiles the whole K-round trajectory into one
    ``lax.scan`` dispatch.  ``record_mixed=True`` keeps per-client mixed
    deltas materialized (single-host only); otherwise the kernel backends
    upgrade to the aggregate-only fast path.  ``chunk``/``interpret``
    tune the Pallas kernels (``interpret=None`` resolves per platform).
    ``stream`` (a ``repro.fl.stream.StreamConfig``) selects the
    event-driven semi-async runtime instead of the synchronous ones.
    ``runtime`` (a ``repro.runtime.RuntimeConfig``, requires ``stream``)
    upgrades the semi-async runtime to the wall-clock ingestion engine:
    client training on worker threads, measured arrivals, and a
    replayable ``Recording`` (``repro.runtime.IngestEngine``).
    ``quant`` (a ``repro.fl.packing.QuantSpec``) turns on quantized
    payload groups -- it overrides a plan-carried ``plan.quant``; either
    source is validated against the effective backend at execute time
    (every local backend quantizes, the mesh only 'fused'/'fused_rs';
    the stream runtime rejects quantization).
    """
    backend: str = "einsum"
    scan: bool = False
    record_mixed: bool = False
    chunk: int = 2048
    interpret: Optional[bool] = None
    jit: bool = True
    mesh: Any = None
    model_cfg: Any = None
    stream: Any = None
    quant: Any = None
    runtime: Any = None


def _check_mesh_quant(quant, backend: str) -> None:
    """Quantized payloads on the mesh need a one-pass schedule (every
    local backend quantizes)."""
    if quant is not None and backend not in ("fused", "fused_rs"):
        raise ValueError(
            "quantized payloads on the mesh runtime require the "
            f"one-pass 'fused' or 'fused_rs' schedules, got {backend!r}")


def resolve_backend(cfg: ExecutionConfig) -> str:
    """Validate ``cfg`` and return the *effective* backend name.

    The entire backend-selection matrix: mesh vs single-host vs stream,
    the record_mixed upgrade to 'aggregate', and every invalid
    combination.
    """
    if cfg.runtime is not None and cfg.stream is None:
        raise ValueError(
            "cfg.runtime (the wall-clock ingestion engine) extends the "
            "semi-async runtime; it requires cfg.stream (a StreamConfig) "
            "for the closure policy")
    if cfg.stream is not None:
        if cfg.mesh is not None:
            raise ValueError("the stream runtime is single-host; "
                             "cfg.mesh is unsupported with cfg.stream")
        if cfg.quant is not None:
            raise ValueError(
                "quantized payloads are not supported on the stream "
                "runtime: stale cohorts re-aggregate deltas from "
                "earlier rounds, which has no well-defined "
                "error-feedback residual; use LocalEngine or MeshEngine")
        if cfg.scan:
            raise ValueError(
                "scan=True contradicts the stream runtime: round closure "
                "is an event-driven host loop, not a lax.scan")
        if cfg.record_mixed:
            raise ValueError(
                "record_mixed is not supported on the stream runtime: "
                "stale cohorts aggregate through combine rows and never "
                "materialize mixed deltas")
        if cfg.backend not in MIXING_BACKENDS:
            raise ValueError(
                f"mixing_backend must be one of {MIXING_BACKENDS}, "
                f"got {cfg.backend!r}")
        if cfg.backend in ("sparse", "sparse_aggregate"):
            raise ValueError(
                "the sparse backends are not supported on the stream "
                "runtime: cohort closure slices dense A_t rows; use "
                "LocalEngine (backend='sparse') or densify the plan")
        # stale cohorts always take the aggregate-only combine-row path
        if cfg.backend == "fused":
            return "aggregate"
        return cfg.backend
    if cfg.mesh is not None:
        if cfg.model_cfg is None:
            raise ValueError("mesh runtime requires model_cfg")
        if cfg.backend not in MIXINGS:
            raise ValueError(f"mesh mixing must be one of {MIXINGS}")
        if cfg.record_mixed:
            raise ValueError(
                "record_mixed is not supported on the mesh runtime: "
                "the mesh train step never returns mixed deltas")
        _check_mesh_quant(cfg.quant, cfg.backend)
        return cfg.backend
    if cfg.backend not in MIXING_BACKENDS:
        raise ValueError(
            f"mixing_backend must be one of {MIXING_BACKENDS}, "
            f"got {cfg.backend!r}")
    if cfg.record_mixed and cfg.backend in ("aggregate",
                                            "sparse_aggregate"):
        raise ValueError(
            f"record_mixed=True contradicts the {cfg.backend!r} backend, "
            "which never materializes mixed deltas")
    # History never records per-client mixed deltas, so unless the caller
    # explicitly keeps them, the kernel backends dispatch the
    # aggregate-only fast path (~3x less payload traffic).
    if not cfg.record_mixed and cfg.backend == "fused":
        return "aggregate"
    if not cfg.record_mixed and cfg.backend == "sparse":
        return "sparse_aggregate"
    return cfg.backend


class Engine(Protocol):
    """A compiled runtime that can execute a ``RoundPlan``."""

    backend: str   # effective backend (post resolve_backend)

    def execute(self, plan: RoundPlan, params: PyTree,
                batches: List[PyTree], *, eval_fn: Optional[EvalFn] = None,
                eval_every: int = 1, energy_ratio: float = 0.1
                ) -> Tuple[PyTree, History]:
        """Run every round of ``plan`` from ``params``.

        ``batches`` is the per-round list (length ``plan.n_rounds``) of
        whatever the runtime's round function consumes -- batch pytrees
        (LocalEngine) or token arrays (MeshEngine).  Returns the final
        params and the filled ``History``.
        """
        ...


def _host_columns(plan: RoundPlan, sparse: bool = False):
    """Plan columns as stacked host arrays in the dtypes the round
    programs take (float32; ELL indices int32).  The sequential drivers
    pass row ``t`` of each as a jit argument, which slices on the host
    and compiles nothing whatever the plan's K.

    ``sparse=True`` (the ELL backends) yields ``A_seq`` as the 2-tuple
    ``(idx_seq, w_seq)`` of (K, n, d_max) arrays -- straight from a
    sparse plan without densifying, converted O(nnz)-wise from a dense
    one.  Dense backends on a sparse plan densify per round (small-n
    parity testing); at scale, keep representation and backend aligned.
    """
    if sparse:
        A = plan.A_t if plan.is_sparse else SparseAseq.from_dense(plan.A_t)
        A_seq = A.ell()
    elif plan.is_sparse:
        A_seq = np.asarray(plan.A_t.dense(), np.float32)
    else:
        A_seq = np.asarray(plan.A_t, np.float32)
    tau_seq = np.asarray(plan.tau_t, np.float32)
    m_seq = np.asarray(plan.m_t, np.float32)
    eta_seq = np.asarray(plan.eta_t, np.float32)
    active_seq = (np.asarray(plan.active_t, np.float32)
                  if plan.has_dropout else None)
    return A_seq, tau_seq, m_seq, eta_seq, active_seq


def _device_columns(plan: RoundPlan, sparse: bool = False):
    """``_host_columns`` as device arrays: the scan drivers' inputs."""
    return jax.tree.map(jnp.asarray, _host_columns(plan, sparse=sparse))


def _cached(programs: Dict[Any, Callable], key, build: Callable[[], Callable]
            ) -> Callable:
    """The program ``programs[key]``, built by ``build()`` on the first
    call for ``key``; counts ``engine.program_cache`` ``hit``/``miss``.
    Each engine keeps its own ``programs``, so a later segment reuses the
    jitted round and jax's dispatch cache hits: nothing is traced,
    lowered or loaded again."""
    program = programs.get(key)
    count("engine.program_cache", "miss" if program is None else "hit")
    if program is None:
        program = programs[key] = build()
    return program


def _bytes_limit() -> Optional[int]:
    """The default device's memory in bytes, where it reports one."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def cohort_form(params: PyTree, n: int, bytes_limit: Optional[int]) -> str:
    """'vmap' where the ``(1 + 2n)`` param copies of the vmapped round
    (the globals, n clients' params and n deltas) fit ``bytes_limit``,
    else 'sequential'; 'vmap' when the limit is unknown."""
    if bytes_limit is None:
        return "vmap"
    size = sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(params))
    return "vmap" if (1 + 2 * n) * size <= bytes_limit else "sequential"


def _quant_setup(cfg: ExecutionConfig, plan: RoundPlan, params: PyTree,
                 backend: str, mesh=None):
    """Resolve the effective quant config (cfg overrides plan) and build
    the round-0 quantizer state.

    The packing spec only reads leaf shapes/dtypes, so it is built from
    ``ShapeDtypeStruct``s of the *delta* tree (deltas share the param
    tree's structure and dtypes) -- the same cache entry the round
    functions hit with real delta trees.  Returns ``(quant, qstate)``,
    both None when neither source configures quantization."""
    quant = cfg.quant if cfg.quant is not None else plan.quant
    if quant is None:
        return None, None
    if mesh is not None:
        _check_mesh_quant(quant, backend)
    from . import packing

    shards = 1
    if mesh is not None and backend == "fused_rs":
        from repro.launch.mesh import data_axis_size
        shards = data_axis_size(mesh)
    n = plan.n_clients
    spec = packing.pack_spec(
        jax.tree.map(lambda p: jax.ShapeDtypeStruct((n,) + p.shape,
                                                    p.dtype), params),
        shards=shards, quant=quant)
    return quant, packing.init_quant_state(spec, n)


def _record(plan: RoundPlan, t: int) -> RoundRecord:
    # t is local to the plan; plan.t0 shifts sliced (resumed) plans so
    # History round indices stay global across a crash/restore boundary
    return RoundRecord(
        t=plan.t0 + t, m=int(plan.m_planned_t[t]),
        m_actual=int(plan.m_actual_t[t]),
        psi_bound=float(plan.psi_bound_t[t]), d2s=int(plan.d2s_t[t]),
        d2d=int(plan.d2d_t[t]), eta=float(plan.eta_t[t]))


def _check_batches(plan: RoundPlan, batches) -> None:
    if len(batches) != plan.n_rounds:
        raise ValueError(
            f"need one batch entry per plan round: plan has "
            f"{plan.n_rounds} rounds, got {len(batches)} batches")


def _append_record(plan: RoundPlan, history: History, t: int, get_params,
                   eval_fn: Optional[EvalFn], eval_every: int) -> None:
    """One ``RoundRecord`` (+ ledger row) for round ``t``;
    ``get_params()`` yields the post-round globals, called only on the
    eval cadence (so drivers never retain params just for bookkeeping)."""
    rec = _record(plan, t)
    if eval_fn is not None and (t % eval_every == 0
                                or t == plan.n_rounds - 1):
        with span("engine.eval", round=rec.t):
            rec.metrics = {k: float(v)
                           for k, v in eval_fn(get_params()).items()}
    history.records.append(rec)
    history.ledger.add_round(d2s=rec.d2s, d2d=rec.d2d)


def _fill_history(plan: RoundPlan, history: History, params_at,
                  eval_fn: Optional[EvalFn], eval_every: int) -> None:
    """Append every round's record; ``params_at(t)`` yields the
    post-round-``t`` params (the scan drivers' stacked ``params_seq``)."""
    for t in range(plan.n_rounds):
        _append_record(plan, history, t, lambda tt=t: params_at(tt),
                       eval_fn, eval_every)


class LocalEngine:
    """Single-host runtime: ``repro.core.rounds`` round functions."""

    def __init__(self, loss_fn, cfg: ExecutionConfig):
        if cfg.mesh is not None:
            raise ValueError("LocalEngine does not take a mesh; use "
                             "MeshEngine (or make_engine)")
        if cfg.stream is not None:
            raise ValueError("LocalEngine is synchronous; use "
                             "StreamEngine (or make_engine) for "
                             "cfg.stream")
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.backend = resolve_backend(cfg)
        # round programs by (path, [K,] cohort, quant), built once
        # (``_cached``)
        self._programs: Dict[Any, Callable] = {}
        # filled by execute_controlled: the realized RoundPlan artifact
        self.last_realized_plan = None

    def _cohort(self, params, n: int) -> str:
        form = cohort_form(params, n, _bytes_limit())
        count("engine.cohort", form)
        return form

    def execute(self, plan, params, batches, *, eval_fn=None, eval_every=1,
                energy_ratio=0.1):
        _check_batches(plan, batches)
        cfg = self.cfg
        K = plan.n_rounds
        sparse = self.backend in ("sparse", "sparse_aggregate")
        with span("engine.prepare"):
            columns = _device_columns if cfg.scan else _host_columns
            A_seq, tau_seq, m_seq, eta_seq, active_seq = columns(
                plan, sparse=sparse)
            history = History(algorithm=plan.algorithm,
                              ledger=CommLedger(energy_ratio=energy_ratio))
            quant, qstate = _quant_setup(cfg, plan, params, self.backend)
            cohort = self._cohort(params, plan.n_clients)
            if cfg.scan:
                scanned = _cached(
                    self._programs, ("scan", K, cohort, quant),
                    lambda: make_scanned_rounds(
                        self.loss_fn, K, jit=cfg.jit,
                        mixing_backend=self.backend, chunk=cfg.chunk,
                        interpret=cfg.interpret, quant=quant,
                        cohort=cohort))
                batches_seq = jax.tree.map(lambda *bs: jnp.stack(bs),
                                           *batches)
            else:
                round_fn = self._round_fn(quant, cohort)

        if cfg.scan:
            with span("engine.dispatch", round=plan.t0):
                if quant is not None:
                    params, params_seq, _ = scanned(
                        params, batches_seq, A_seq, tau_seq, m_seq,
                        eta_seq, active_seq, qstate)
                else:
                    params, params_seq = scanned(
                        params, batches_seq, A_seq, tau_seq, m_seq,
                        eta_seq, active_seq)
            _fill_history(plan, history,
                          lambda t: jax.tree.map(lambda x: x[t], params_seq),
                          eval_fn, eval_every)
            return params, history

        for t in range(K):
            with span("engine.dispatch", round=plan.t0 + t):
                A_arg = ((A_seq[0][t], A_seq[1][t]) if sparse
                         else A_seq[t])
                args = (params, batches[t], A_arg, tau_seq[t], m_seq[t],
                        eta_seq[t])
                if active_seq is not None or quant is not None:
                    args = args + (active_seq[t] if active_seq is not None
                                   else None,)
                if quant is not None:
                    params, _, qstate = round_fn(*args, qstate)
                else:
                    params, _ = round_fn(*args)
            # record inline: only the current round's params stay live
            _append_record(plan, history, t, lambda p=params: p,
                           eval_fn, eval_every)
        return params, history

    def _round_fn(self, quant, cohort):
        """This engine's per-round program for ``quant`` and ``cohort``."""
        cfg = self.cfg
        return _cached(
            self._programs, ("round", cohort, quant),
            lambda: make_round_fn(self.loss_fn, jit=cfg.jit,
                                  mixing_backend=self.backend,
                                  chunk=cfg.chunk, interpret=cfg.interpret,
                                  quant=quant, cohort=cohort))

    def execute_controlled(self, loop, params, batches, *, eval_fn=None,
                           eval_every=1, energy_ratio=0.1):
        """Closed-loop execution: one ``repro.control.ControlLoop`` row
        per round, realized through the same jitted round function as
        ``execute`` with per-round arrays carrying identical
        values -- so replaying ``self.last_realized_plan`` (set on
        return) through ``execute`` reproduces this run bitwise (the
        replay's records merely lack the live ``control`` telemetry).

        When the policy consumes training feedback
        (``loop.needs_deltas``, the learned-graph path), each round's
        client deltas are re-derived from the pre-round params and fed
        back after the round -- one extra deltas evaluation per round,
        the documented price of the alternating model/graph scheme.
        """
        cfg = self.cfg
        if cfg.scan:
            raise ValueError(
                "controlled execution is inherently per-round (the "
                "policy observes each realized topology); scan=True is "
                "unsupported")
        if cfg.quant is not None:
            raise ValueError(
                "controlled execution does not support quantized "
                "payloads: the realized plan carries no quant spec to "
                "replay the error-feedback residuals against")
        sparse = self.backend in ("sparse", "sparse_aggregate")
        if bool(getattr(loop, "_sparse")) != sparse:
            raise ValueError(
                f"loop sparsity ({getattr(loop, '_sparse')}) must match "
                f"the engine backend {self.backend!r} ({sparse})")
        K = len(batches)
        history = History(algorithm=loop.algorithm,
                          ledger=CommLedger(energy_ratio=energy_ratio))
        round_fn = self._round_fn(None, self._cohort(params, loop.n))
        needs_deltas = loop.needs_deltas
        for t in range(K):
            row, telemetry = loop.next_row()
            deltas = None
            if needs_deltas:
                # pre-round params: the deltas the round itself mixes
                from repro.core.rounds import client_deltas
                tree = client_deltas(self.loss_fn, params, batches[t],
                                     row.eta)
                deltas = np.concatenate(
                    [np.asarray(leaf).reshape(loop.n, -1)
                     for leaf in jax.tree.leaves(tree)], axis=1)
            if sparse:
                idx, w = row.A.ell()
                A_arg = (jnp.asarray(idx), jnp.asarray(w))
            else:
                A_arg = jnp.asarray(row.A, jnp.float32)
            params, _ = round_fn(
                params, batches[t], A_arg,
                jnp.asarray(row.tau, jnp.float32),
                jnp.asarray(row.m, jnp.float32),
                jnp.asarray(row.eta, jnp.float32))
            rec = RoundRecord(
                t=row.t, m=row.m_planned, m_actual=row.m_actual,
                psi_bound=row.psi_bound, d2s=row.d2s, d2d=row.d2d,
                eta=row.eta, control=telemetry)
            if eval_fn is not None and (t % eval_every == 0 or t == K - 1):
                rec.metrics = {k: float(v)
                               for k, v in eval_fn(params).items()}
            history.records.append(rec)
            history.ledger.add_round(d2s=rec.d2s, d2d=rec.d2d)
            loop.feed(rec, deltas)
        self.last_realized_plan = loop.emit_plan()
        return params, history


class MeshEngine:
    """Mesh runtime: ``repro.fl.distributed`` train steps.  ``batches``
    entries are per-round token arrays ``(n_clients, T, B_local, S+1)``."""

    def __init__(self, cfg: ExecutionConfig):
        if cfg.mesh is None:
            raise ValueError("MeshEngine requires cfg.mesh")
        if cfg.stream is not None:
            raise ValueError("MeshEngine is synchronous; cfg.stream is "
                             "unsupported on the mesh runtime")
        self.cfg = cfg
        self.backend = resolve_backend(cfg)
        # train steps by (path, [K,] quant), built once (``_cached``)
        self._programs: Dict[Any, Callable] = {}

    def execute(self, plan, params, batches, *, eval_fn=None, eval_every=1,
                energy_ratio=0.1):
        _check_batches(plan, batches)
        cfg = self.cfg
        K = plan.n_rounds
        with span("engine.prepare"):
            columns = _device_columns if cfg.scan else _host_columns
            A_seq, tau_seq, m_seq, eta_seq, active_seq = columns(plan)
            history = History(algorithm=plan.algorithm,
                              ledger=CommLedger(energy_ratio=energy_ratio))
            quant, qstate = _quant_setup(cfg, plan, params, self.backend,
                                         mesh=cfg.mesh)
            if cfg.scan:
                scanned = _cached(
                    self._programs, ("scan", K, quant),
                    lambda: make_scanned_train_steps(
                        cfg.model_cfg, cfg.mesh, K, mixing=self.backend,
                        jit=cfg.jit, quant=quant))
                tokens_seq = jax.tree.map(lambda *bs: jnp.stack(bs),
                                          *batches)
            else:
                step = _cached(
                    self._programs, ("step", quant),
                    lambda: make_train_step(cfg.model_cfg, cfg.mesh,
                                            mixing=self.backend, jit=cfg.jit,
                                            quant=quant))

        if cfg.scan:
            with span("engine.dispatch", round=plan.t0):
                if quant is not None:
                    params, params_seq, _ = scanned(
                        params, tokens_seq, A_seq, tau_seq, m_seq, eta_seq,
                        active_seq=active_seq, qstate=qstate)
                else:
                    params, params_seq = scanned(
                        params, tokens_seq, A_seq, tau_seq, m_seq, eta_seq,
                        active_seq=active_seq)
            _fill_history(plan, history,
                          lambda t: jax.tree.map(lambda x: x[t], params_seq),
                          eval_fn, eval_every)
            return params, history

        for t in range(K):
            with span("engine.dispatch", round=plan.t0 + t):
                kw = {} if active_seq is None else {"active": active_seq[t]}
                if quant is not None:
                    params, qstate = step(params, batches[t], A_seq[t],
                                          tau_seq[t], m_seq[t], eta_seq[t],
                                          qstate=qstate, **kw)
                else:
                    params = step(params, batches[t], A_seq[t], tau_seq[t],
                                  m_seq[t], eta_seq[t], **kw)
            _append_record(plan, history, t, lambda p=params: p,
                           eval_fn, eval_every)
        return params, history


def make_engine(cfg: ExecutionConfig, loss_fn=None) -> Engine:
    """ExecutionConfig -> the engine that implements it.  The only
    runtime dispatch the server (or any driver) needs."""
    if cfg.stream is not None:
        # deferred: stream imports back into this module at class init
        if cfg.runtime is not None:
            from repro.runtime import IngestEngine
            return IngestEngine(loss_fn, cfg)
        from .stream import StreamEngine
        return StreamEngine(loss_fn, cfg)
    if cfg.mesh is not None:
        return MeshEngine(cfg)
    return LocalEngine(loss_fn, cfg)
