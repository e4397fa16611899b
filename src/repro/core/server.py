"""Parameter-server orchestration: plan -> engine -> History.

The server is a thin host-side driver around two first-class objects:

* ``repro.fl.plan.RoundPlan`` -- the full time-varying trajectory
  ``(A_t, tau_t, m_t, eta_t, active_t)`` as stacked host arrays, built by
  the algorithm constructors (``connectivity_aware`` = Algorithm 1 with
  the eq.-7 m(t) rule, ``fedavg`` = A I / fixed m, ``colrel``) and
  serializable to JSON for reproducible runs.
* ``repro.fl.engine.Engine`` -- the compiled runtime that executes a
  plan: ``LocalEngine`` (single-host ``core.rounds``) or ``MeshEngine``
  (``fl.distributed``), selected by one ``ExecutionConfig(backend=,
  scan=, record_mixed=, chunk=, interpret=, mesh=, model_cfg=)``.  The
  backend-selection matrix lives in ``repro.fl.engine.resolve_backend``
  and nowhere else.

``run()`` is therefore just::

    plan  = RoundPlan.<algorithm>(network, config) -- planned on its own
            seeded rng stream, so the seed embeds and the plan is
            *regenerable* -- or a caller-provided plan (``run(plan=...)``,
            e.g. one loaded from JSON)
    self.params, history = engine.execute(plan, params, batches, ...)

Planning and batch sampling draw from SPLIT rng streams: planning from
``default_rng(config.seed)`` (owned by the ``RoundPlan`` constructors,
embedded in the plan for ``plan.regenerate()``), batches from the
derived stream ``default_rng([config.seed, 1])``.  Because the batch
stream no longer interleaves with planning draws, replaying a saved
plan (``run(plan=...)``) consumes the batch stream identically to the
original planning run -- same seed, same batches, bitwise.

Straggler masks (``active_t``) are a plan column, not a runtime flag:
``plan.with_dropout(rate)`` drops clients per round, the engines thread
the mask through every mixing backend, and an all-ones mask is
bitwise-identical to full participation.

Legacy construction kwargs (``mixing_backend=``, ``scan_rounds=``,
``record_mixed=``, ``mesh=``, ``model_cfg=``, ``chunk=``,
``interpret=``) still work: they are translated to an ``ExecutionConfig``
under a ``DeprecationWarning``.  Pass ``execution=ExecutionConfig(...)``
instead.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.spans import span

from .metrics import CommLedger

__all__ = ["ServerConfig", "RoundRecord", "History", "FederatedServer"]

PyTree = Any
BatchSampler = Callable[[np.random.Generator, int], PyTree]
EvalFn = Callable[[PyTree], Dict[str, float]]
EtaSchedule = Callable[[int], float]

ALGORITHMS = ("semidec", "fedavg", "colrel")


@dataclasses.dataclass
class ServerConfig:
    T: int = 5                      # local SGD iterations per global round
    t_max: int = 30                 # number of global rounds
    phi_max: float = 0.06           # connectivity-factor threshold (Alg. 1 input)
    m0: Optional[int] = None        # initial sample size (default: n)
    m_fixed: Optional[int] = None   # fedavg / colrel sample size
    bound_kind: str = "auto"        # 'regular' (5.1) | 'general' (5.2) | 'auto'
                                    # | 'verbatim' (eq. 6 incl. +1)
                                    # | 'exact' (oracle sigma from topology)
    energy_ratio: float = 0.1       # E_D2D / E_Glob
    seed: int = 0
    eta: EtaSchedule = dataclasses.field(
        default_factory=lambda: (lambda t: 0.02 * (0.1 ** t)))  # paper Sec. 6.1.3


@dataclasses.dataclass
class RoundRecord:
    t: int
    m: int
    m_actual: int
    psi_bound: float      # server's bound on the connectivity factor (eq. 6)
    d2s: int
    d2d: int
    eta: float
    metrics: Dict[str, float] = dataclasses.field(default_factory=dict)
    # streaming telemetry (repro.fl.stream): deadline hits, late/lost/
    # duplicate uploads, staleness stats, weighted divisor, shortfall.
    # None for every synchronous round, so a fault-free semi-async run
    # records bit-identical History to the synchronous engines.
    stream: Optional[Dict[str, float]] = None
    # control telemetry (repro.control): realized per-cluster phi, the
    # open-loop m rule vs the decided m, gossip depth.  None for every
    # open-loop round AND for replays of a controlled run's emitted
    # plan -- replay equality checks compare everything but this field.
    control: Optional[Dict[str, float]] = None


@dataclasses.dataclass
class History:
    algorithm: str
    records: List[RoundRecord] = dataclasses.field(default_factory=list)
    ledger: CommLedger = dataclasses.field(default_factory=CommLedger)

    def series(self, key: str) -> np.ndarray:
        return np.array([r.metrics.get(key, np.nan) for r in self.records])

    @property
    def sample_sizes(self) -> np.ndarray:
        return np.array([r.m for r in self.records])

    def cumulative_cost(self) -> np.ndarray:
        return self.ledger.cumulative_cost()


_LEGACY_KWARGS = ("mixing_backend", "scan_rounds", "record_mixed", "mesh",
                  "model_cfg", "chunk", "interpret")


class FederatedServer:
    """Runs ``t_max`` global rounds of the chosen algorithm.

    ``network`` is any ``repro.topology.TopologyModel`` (the registered
    families, or the deprecated ``D2DNetwork`` shim).  ``execution`` (an
    ``repro.fl.engine.ExecutionConfig``) selects the runtime; the legacy
    per-knob kwargs translate to it under a ``DeprecationWarning``.
    After ``run()``, ``self.last_plan`` holds the executed ``RoundPlan``
    (save it with ``last_plan.save(path)`` to pin the trajectory).
    """

    def __init__(self, network, loss_fn, init_params: PyTree,
                 batch_sampler: BatchSampler, config: ServerConfig,
                 algorithm: str = "semidec", jit: Optional[bool] = None,
                 execution=None,
                 mixing_backend: Optional[str] = None,
                 scan_rounds: Optional[bool] = None,
                 record_mixed: Optional[bool] = None,
                 mesh=None, model_cfg=None,
                 chunk: Optional[int] = None,
                 interpret: Optional[bool] = None):
        # deferred: repro.fl imports back into repro.core at package init
        from repro.fl.engine import ExecutionConfig, make_engine

        if algorithm not in ALGORITHMS:
            raise ValueError(f"algorithm must be one of {ALGORITHMS}")
        if algorithm in ("fedavg", "colrel") and config.m_fixed is None:
            raise ValueError(f"{algorithm} requires config.m_fixed")

        passed = dict(zip(_LEGACY_KWARGS,
                          (mixing_backend, scan_rounds, record_mixed,
                           mesh, model_cfg, chunk, interpret)))
        legacy = {k: v for k, v in passed.items() if v is not None}
        if execution is not None:
            if legacy:
                raise ValueError(
                    "pass either execution=ExecutionConfig(...) or the "
                    f"legacy kwargs {sorted(legacy)}, not both")
            if jit is not None and jit != execution.jit:
                raise ValueError(
                    f"jit={jit} contradicts execution.jit="
                    f"{execution.jit}; set jit on the ExecutionConfig")
        else:
            if legacy:
                warnings.warn(
                    f"FederatedServer kwargs {sorted(legacy)} are "
                    "deprecated; pass execution=ExecutionConfig("
                    "backend=, scan=, record_mixed=, chunk=, interpret=, "
                    "mesh=, model_cfg=) instead",
                    DeprecationWarning, stacklevel=2)
            execution = ExecutionConfig(
                backend=mixing_backend if mixing_backend is not None
                else "einsum",
                scan=bool(scan_rounds),
                record_mixed=bool(record_mixed),
                chunk=chunk if chunk is not None else 2048,
                interpret=interpret,
                jit=jit if jit is not None else True,
                mesh=mesh, model_cfg=model_cfg)

        self.network = network
        self.config = config
        self.algorithm = algorithm
        self.params = init_params
        self.batch_sampler = batch_sampler
        self.execution = execution
        self.engine = make_engine(execution, loss_fn)
        # batch stream only; planning owns default_rng(config.seed) so
        # the plan seed embeds and server-built plans regenerate()
        self.rng = np.random.default_rng([config.seed, 1])
        self.last_plan = None

    @property
    def effective_backend(self) -> str:
        """The backend the engine actually dispatches (post
        ``resolve_backend``, e.g. 'fused' upgraded to 'aggregate')."""
        return self.engine.backend

    # -- plan + batches (split rng streams: plan seeded, batches derived) --

    def _plan_and_batches(self, plan=None):
        """Build (or adopt) the trajectory and draw the per-round batches.

        Planning runs on its own seeded stream (inside the ``RoundPlan``
        constructors, which therefore embed ``config.seed`` as
        regenerable provenance); batches always come from ``self.rng``,
        so a replayed plan consumes the batch stream exactly like the
        planning run did."""
        from repro.fl.plan import RoundPlan

        cfg = self.config
        if plan is None:
            ctor = {"semidec": RoundPlan.connectivity_aware,
                    "fedavg": RoundPlan.fedavg,
                    "colrel": RoundPlan.colrel}[self.algorithm]
            plan = ctor(self.network, cfg)
        elif plan.n_clients != self.network.n:
            raise ValueError(
                f"plan is for {plan.n_clients} clients, network has "
                f"{self.network.n}")
        with span("server.batches"):
            batches = [self.batch_sampler(self.rng, t)
                       for t in range(plan.n_rounds)]
        return plan, batches

    @span("server.run")
    def run(self, eval_fn: Optional[EvalFn] = None, eval_every: int = 1,
            plan=None, controller=None) -> History:
        """build plan -> engine.execute(plan) -> History.

        ``plan``: an explicit ``RoundPlan`` to execute (e.g. loaded from
        JSON, or a built plan transformed by ``with_dropout``); default
        is to plan ``config.t_max`` rounds of ``self.algorithm`` here.

        ``controller``: close the loop instead of planning open-loop --
        a ``repro.control`` policy (family string like
        ``'threshold:phi_max=0.2'``, a ``ControllerSpec``, or a built
        ``Controller``) decides each round's sample size / gossip depth
        / step size online from the realized topology.  Mutually
        exclusive with ``plan``; requires an engine with a
        ``execute_controlled`` method (``LocalEngine``/``StreamEngine``).
        Afterwards ``self.last_plan`` holds the *realized* plan emitted
        by the control loop -- replaying it through ``run(plan=...)``
        reproduces the controlled run bitwise (modulo the
        ``RoundRecord.control`` telemetry, which only the live run has).
        """
        if controller is not None:
            if plan is not None:
                raise ValueError(
                    "pass either plan= or controller=, not both: a "
                    "controller generates its own realized plan")
            if self.algorithm != "semidec":
                raise ValueError(
                    "controllers drive the connectivity-aware algorithm "
                    f"only (algorithm='semidec'), got {self.algorithm!r}")
            execute_controlled = getattr(self.engine,
                                         "execute_controlled", None)
            if execute_controlled is None:
                raise ValueError(
                    f"{type(self.engine).__name__} does not support "
                    "controlled execution (no execute_controlled); use "
                    "LocalEngine or StreamEngine")
            from repro.control import ControlLoop

            sparse = self.effective_backend in ("sparse",
                                                "sparse_aggregate")
            loop = ControlLoop(self.network, self.config, controller,
                               algorithm=self.algorithm, sparse=sparse)
            with span("server.batches"):
                batches = [self.batch_sampler(self.rng, t)
                           for t in range(self.config.t_max)]
            self.params, history = execute_controlled(
                loop, self.params, batches, eval_fn=eval_fn,
                eval_every=eval_every,
                energy_ratio=self.config.energy_ratio)
            self.last_plan = self.engine.last_realized_plan
            return history
        plan, batches = self._plan_and_batches(plan)
        self.params, history = self.engine.execute(
            plan, self.params, batches, eval_fn=eval_fn,
            eval_every=eval_every, energy_ratio=self.config.energy_ratio)
        self.last_plan = plan
        return history
