"""System under test: the paper's FL round on the McMahan et al. CNN,
driven through ``FederatedServer.run(plan=...)`` as the train CLI runs it.

Set-up builds one ``FederatedServer`` (weights made on the device from
the seed in one jitted call), then runs a first segment of
``CHECK_ROUNDS`` rounds through the same call the window makes: that
compiles every program the window uses, and those rounds are the first
ones the reference follows.  The window then runs segments 1, 2, ...
of ``segment_rounds`` rounds until ``seconds`` have passed, each with a
plan built from the seed and the segment index; params and the batch
stream carry over from segment to segment.  The reference also follows
the first and the last ``CHECK_ROUNDS`` rounds of the window's last
segment: the first from the params the segment before it ended with
(so a segment that does not carry them over shows), the last up to the
window's final params.

Every round ends with its params ready: the per-round eval reads them.
"""

from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import costs
from bench.harness.compare import Check, comm_check, training_numbers

CHECK_ROUNDS = 3


def derive(seed: int, *tags: int) -> int:
    """A 32-bit seed for one purpose, from the run's seed (any size)."""
    return int(np.random.SeedSequence([seed, *tags]).generate_state(1)[0])


def init_params(key, model: Dict[str, Any]):
    """He-normal weights and zero biases with the program's tree layout,
    made on the device in one call."""
    hw, c = model["image_hw"], model["channels"]
    c1, c2, k = model["conv1"], model["conv2"], model["kernel"]
    flat = (hw // 4) * (hw // 4) * c2
    h, o = model["fc_hidden"], model["n_classes"]
    shapes = {"conv1": ((k, k, c, c1), k * k * c),
              "conv2": ((k, k, c1, c2), k * k * c1),
              "fc1": ((flat, h), flat), "fc2": ((h, o), h)}
    keys = jax.random.split(key, len(shapes))
    out = {}
    for kk, (name, (shape, fan_in)) in zip(keys, sorted(shapes.items())):
        w = jax.random.normal(kk, shape, jnp.float32) * np.sqrt(2.0 / fan_in)
        out[name] = {"w": w, "b": jnp.zeros(shape[-1], jnp.float32)}
    return out


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    stamps: List[float]        # when each round's params were ready
    rounds: int


@dataclasses.dataclass
class Span:
    """Rounds of a segment as the reference follows them: the params they
    started from, their batches and plan rows, and the params and test
    loss after each."""
    x0: Any = None
    batches: List[Any] = dataclasses.field(default_factory=list)
    rows: List[Any] = dataclasses.field(default_factory=list)
    params: List[Any] = dataclasses.field(default_factory=list)
    losses: List[float] = dataclasses.field(default_factory=list)

    def to_host(self) -> "Span":
        host = partial(jax.tree.map, np.asarray)
        return Span(host(self.x0), [host(b) for b in self.batches],
                    self.rows, [host(p) for p in self.params],
                    list(self.losses))


class System:
    """``devices`` are the chips the cell may use; this one-chip system
    runs on the default device."""

    def __init__(self, cell, seed: int, spans, devices):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans = spans
        self._stamps: List[float] = []     # when each round was ready
        self.plans: List[Any] = []         # every plan run, for comm checks
        self.histories: List[Any] = []
        self.first: List[Span] = []        # set-up's rounds
        self.last: List[Span] = []         # the window's last segment's
        self._ref: Dict[int, Any] = {}

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        from repro import topology
        from repro.core.server import FederatedServer, ServerConfig
        from repro.data import (FederatedBatcher, label_sorted_partition,
                                make_classification)
        from repro.fl import ExecutionConfig
        from repro.models import cnn

        pop, tr, model = (self.cfg["population"], self.cfg["training"],
                          self.cfg["model"])
        seed = self.seed
        ds_train = make_classification(
            n_samples=pop["train_samples"], n_classes=model["n_classes"],
            image_hw=model["image_hw"], seed=derive(seed, 1))
        ds_test = make_classification(
            n_samples=pop["test_samples"], n_classes=model["n_classes"],
            image_hw=model["image_hw"], seed=derive(seed, 2))
        # a share of the training labels drawn at random, so that local SGD
        # keeps learning through the window (the held-out set is clean)
        flip_rng = np.random.default_rng(derive(seed, 4))
        flip = flip_rng.random(len(ds_train.y)) < pop["label_noise"]
        ds_train.y[flip] = flip_rng.integers(0, model["n_classes"],
                                             int(flip.sum()))
        parts = label_sorted_partition(
            ds_train, pop["n"], shards_per_client=pop["shards_per_client"],
            rng=np.random.default_rng(derive(seed, 3)))
        batcher = FederatedBatcher(ds_train, parts, T=tr["T"],
                                   batch_size=tr["batch"])
        self.test_set = (ds_test.x, ds_test.y)
        self._batches: Dict[int, Any] = {}

        def sampler(rng, t):
            out = batcher(rng, t)
            self._batches[t] = out
            return out

        key = jax.random.wrap_key_data(np.asarray(
            np.random.SeedSequence([seed, 7]).generate_state(2), np.uint32))
        params0 = jax.jit(partial(init_params, model=model))(key)
        self._end = params0           # the params the last round ended with

        apply_fn = cnn.cnn_apply
        loss_fn = partial(cnn.l2_regularized_loss, apply_fn,
                          mu=model["l2_mu"])
        xs, ys = jnp.asarray(ds_test.x), jnp.asarray(ds_test.y)

        def eval_fn(p):
            return {"test_acc": cnn.accuracy(apply_fn, p, xs, ys),
                    "test_loss": float(loss_fn(p, (xs, ys)))}

        self._eval = self.spans.wrap("eval", eval_fn)
        spec = topology.make_spec(
            pop["topology"], n=pop["n"], c=pop["clusters"],
            k_range=(pop["k_min"], pop["k_max"]), p_fail=pop["p_fail"])
        self.network = spec.build()
        eta = float(tr["eta"])
        self._server_cfg = partial(
            ServerConfig, T=tr["T"], t_max=self.traffic["segment_rounds"],
            phi_max=tr["phi_max"], eta=lambda t: eta)
        execution = ExecutionConfig(backend=self.traffic["backend"])
        self.server = FederatedServer(
            self.network, loss_fn, params0,
            self.spans.wrap("input", sampler),
            self._server_cfg(seed=seed), execution=execution)

        # the first rounds, followed by the reference, and a warm-up of
        # every program the window runs
        self.first = self._run_segment(0, CHECK_ROUNDS)

    def _run_segment(self, segment: int, rounds: int) -> List[Span]:
        """Run one segment through ``FederatedServer.run``; returns its
        first and its last ``CHECK_ROUNDS`` rounds (one span where they
        are the same), as device arrays, not copied."""
        from repro.fl import RoundPlan

        with self.spans.span("plan"):
            plan = RoundPlan.connectivity_aware(
                self.network, self._server_cfg(seed=derive(self.seed, segment),
                                               t_max=rounds))
        spans = {s: Span() for s in sorted({0, rounds - CHECK_ROUNDS})}
        spans[0].x0 = self._end
        self._batches.clear()
        calls = [0]

        def hook(p):
            t = calls[0]
            calls[0] += 1
            out = self._eval(p)
            self._stamps.append(time.perf_counter())
            self._end = p
            for s, span in spans.items():
                if t == s - 1:
                    span.x0 = p
                elif s <= t < s + CHECK_ROUNDS:
                    span.params.append(p)
                    span.losses.append(out["test_loss"])
            return out

        with self.spans.span("segment"):
            history = self.server.run(eval_fn=hook, plan=plan)
        for s, span in spans.items():
            span.batches = [self._batches[t]
                            for t in range(s, s + CHECK_ROUNDS)]
            span.rows = [plan[t] for t in range(s, s + CHECK_ROUNDS)]
        self._batches.clear()
        self.plans.append(plan)
        self.histories.append(history)
        return list(spans.values())

    # -- window ----------------------------------------------------------

    def window(self, seconds: float) -> Window:
        self._stamps = []
        t0 = time.perf_counter()
        segment = 1
        with self.spans.span("window"):
            while True:
                self.last = self._run_segment(
                    segment, self.traffic["segment_rounds"])
                segment += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        stamps = list(self._stamps)
        return Window(t0=t0, t1=stamps[-1], stamps=stamps,
                      rounds=len(stamps))

    def release(self) -> None:
        """Copy what the reference follows to the host and free the
        program's device state before the reference runs."""
        self.first = [s.to_host() for s in self.first]
        self.last = [s.to_host() for s in self.last]
        self.server = None
        self._end = None
        self._eval = None
        self._batches = {}

    # -- costs -------------------------------------------------------------

    def train_flops_per_round(self) -> float:
        pop, tr = self.cfg["population"], self.cfg["training"]
        return float(costs.cnn_train_flops(self.cfg["model"]) * pop["n"]
                     * tr["T"] * tr["batch"])

    def aggregate_bytes_per_call(self) -> float:
        """Bytes the aggregate kernel must move each round: the (n, P)
        fp32 payload once and the fp32 aggregate row, P unpadded."""
        p = costs.cnn_param_count(self.cfg["model"])
        return float(costs.traffic_model(self.cfg["population"]["n"], p,
                                         4)["bytes_agg_only"])

    # -- correctness -------------------------------------------------------

    def reference_run(self, reference, span: Span, dtype: str = "float32",
                      fault=None):
        """The reference over ``span``'s rounds from its start params:
        params after each round and the test loss after each."""
        model = self.cfg["model"]
        return reference.run_rounds(
            model, span.x0, span.batches, span.rows, self.test_set,
            eta=self.cfg["training"]["eta"], mu=model["l2_mu"],
            dtype=dtype, fault=fault)

    def numbers(self, reference, dtype: Optional[str] = None,
                fault=None) -> Dict[str, float]:
        """The numbers compared, each the worst over the spans followed
        (set-up's first rounds, the window's last segment's first and
        last rounds): the program's (``dtype`` None), or those of the
        reference put in its place at ``dtype`` with ``fault``, each
        against the float32 reference."""
        out: Dict[str, float] = {}
        for i, span in enumerate(self.first + self.last):
            if i not in self._ref:
                self._ref[i] = self.reference_run(reference, span)
            ref_params, ref_losses = self._ref[i]
            if dtype is None:
                params, losses = span.params, span.losses
            else:
                params, losses = self.reference_run(reference, span,
                                                    dtype=dtype, fault=fault)
            got = training_numbers(span.x0, params, losses, ref_params,
                                   ref_losses)
            for k, v in got.items():
                v = math.inf if math.isnan(v) else v
                out[k] = max(out.get(k, v), v)
        return out

    def checks(self, reference, limits) -> List[Check]:
        out = [Check(k, v, limits[k])
               for k, v in self.numbers(reference).items() if k in limits]
        out.append(comm_check(self.plans, self.histories, reference.comm,
                              limits))
        return out
