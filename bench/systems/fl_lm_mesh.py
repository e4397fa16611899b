"""System under test: the paper's FL round with StableLM-2-1.6B clients,
one client per chip of a (data=4, model=1) mesh, driven through
``FederatedServer.run(plan=...)`` on ``MeshEngine`` (``ExecutionConfig(
mesh=, model_cfg=, backend="fused_rs")``).

Set-up first checks that the program's model is the published one (the
configuration's keys, and the LayerNorm shifts and q/k/v biases in its
params tree), and raises if not.  It then draws the token stream, builds
one ``FederatedServer`` (weights made on the chips from the seed in one
jitted call) and runs a first segment of ``CHECK_ROUNDS`` rounds through
the same call the window makes, which compiles every program the window
uses.  The window runs segments 1, 2, ... of ``segment_rounds`` rounds,
each with a plan built from the seed and the segment index; params and
the batch stream carry over.  A segment is the window's last when the
time already spent and the shortest segment so far reach ``seconds``.

The reference follows the first ``CHECK_ROUNDS`` rounds of that last
segment from the params the segment before it ended with, so a segment
that does not carry them over shows.  At this size four param copies on
a chip are 13 GB, so those rounds are copied to the host as they end.

Every round ends with the held-out loss, one 2048-token sequence a chip:
the round's sync.
"""

from __future__ import annotations

import dataclasses
import math
import sys
import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.compare import Check, comm_check, training_numbers
from bench.systems.fl_cnn import Window, derive

CHECK_ROUNDS = 3
CHUNK = 1 << 22          # elements per host chunk of the comparison's norms


def model_config(cfg: Dict[str, Any]):
    """The program's config for the configuration's model: its registry
    entry at the configuration's widths, depth and params dtype.  Raises
    if the program's block is not the published one."""
    from repro.configs import get_config
    from repro.models.model import Model

    m = cfg["model"]
    base = get_config(m["arch"])
    published = {
        "norm_type": "layer", "norm_eps": m["layer_norm_eps"],
        "qkv_bias": m["use_qkv_bias"], "qk_norm": m["qk_layernorm"],
        "rope_fraction": m["partial_rotary_factor"],
        "rope_theta": m["rope_theta"], "mlp_type": "swiglu",
        "tie_embeddings": m["tie_word_embeddings"]}
    wrong = {k: (getattr(base, k), v) for k, v in published.items()
             if getattr(base, k) != v}
    if wrong:
        raise ValueError(f"the program's {m['arch']} is not the published "
                         f"block (program, published): {wrong}")
    mc = dataclasses.replace(
        base, n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        d_ff=m["intermediate_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        vocab_size=m["vocab_size"], dtype=m["params_dtype"])
    shapes = jax.eval_shape(Model(mc).init, jax.random.key(0))
    layer = shapes["decoder"]["layers"]
    norms = (layer["ln1"], layer["ln2"], shapes["final_norm"])
    if not (all(isinstance(p, dict) and "bias" in p for p in norms)
            and all("b" in layer["attn"][k] for k in ("q", "k", "v"))):
        raise ValueError(f"the program's {m['arch']} params lack the "
                         "published LayerNorm shifts or q/k/v biases")
    return mc


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward and backward FLOPs of one trained token: 6 per matmul
    parameter (the layers' projections and the head; the embedding is a
    gather) and attention's 12 S d a layer; recomputation not counted."""
    d, ff, L = m["hidden_size"], m["intermediate_size"], \
        m["num_hidden_layers"]
    matmul = L * (4 * d * d + 3 * d * ff) + d * m["vocab_size"]
    return 6.0 * matmul + 12.0 * seq * d * L


@dataclasses.dataclass
class Span:
    """Rounds the reference follows: the params they started from, their
    batches and plan rows, and the program's params after the first and
    the last of them and its held-out loss after each (host arrays)."""
    x0: Any = None
    batches: List[Any] = dataclasses.field(default_factory=list)
    rows: List[Any] = dataclasses.field(default_factory=list)
    first: Any = None
    last: Any = None
    losses: List[float] = dataclasses.field(default_factory=list)


def _host(tree):
    return jax.tree.map(np.asarray, tree)


class System:
    """``devices`` are the chips the cell may use: one client each."""

    def __init__(self, cell, seed: int, spans, devices):
        from jax.sharding import Mesh

        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans = spans
        self.model_cfg = model_config(self.cfg)
        n = self.cfg["population"]["n"]
        self.mesh = Mesh(np.asarray(list(devices)[:n]).reshape(n, 1),
                         ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self._stamps: List[float] = []
        self.plans: List[Any] = []         # every plan run, for comm checks
        self.histories: List[Any] = []
        self.span: Optional[Span] = None   # the rounds the reference follows
        self._ref = None
        self._step_text: Optional[str] = None

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro import topology
        from repro.core.server import FederatedServer, ServerConfig
        from repro.data import lm_batches, make_token_stream
        from repro.fl import ExecutionConfig
        from repro.models.model import Model

        m, pop, tr = (self.cfg["model"], self.cfg["population"],
                      self.cfg["training"])
        n, S, B = pop["n"], self.traffic["seq_len"], self.traffic["batch"]
        E = self.traffic["eval_sequences"]
        seed = self.seed
        # one stream; the clients' regions first, the held-out tail last
        stream = make_token_stream(pop["train_tokens"] + E * (S + 1),
                                   vocab=m["vocab_size"],
                                   order=pop["markov_order"],
                                   seed=derive(seed, 1))
        train = stream[:pop["train_tokens"]]
        self.test_set = stream[pop["train_tokens"]:].reshape(E, S + 1)
        self._batches: Dict[int, np.ndarray] = {}

        def sampler(rng, t):
            xs, ys = lm_batches(train, rng, n, tr["T"], B, S)
            window = np.concatenate([np.asarray(xs),
                                     np.asarray(ys)[..., -1:]], axis=-1)
            self._batches[t] = window
            return jnp.asarray(window)

        model = Model(self.model_cfg)
        replicated = NamedSharding(self.mesh, P())
        key = jax.random.wrap_key_data(np.asarray(
            np.random.SeedSequence([seed, 7]).generate_state(2), np.uint32))
        params0 = jax.jit(model.init, out_shardings=replicated)(key)
        self._end = params0

        test = jax.device_put(self.test_set,
                              NamedSharding(self.mesh, P("data")))
        test_batch = (test[:, :-1], test[:, 1:])
        test_loss = jax.jit(model.loss)

        def eval_fn(p):
            return {"test_loss": float(test_loss(p, test_batch))}

        self._eval = self.spans.wrap("eval", eval_fn)
        self.network = topology.make_spec(
            pop["topology"], n=n, c=pop["clusters"],
            k_range=(pop["k_min"], pop["k_max"]),
            p_fail=pop["p_fail"]).build()
        eta = float(tr["eta"])
        self._server_cfg = partial(
            ServerConfig, T=tr["T"], t_max=self.traffic["segment_rounds"],
            phi_max=tr["phi_max"], eta=lambda t: eta)
        execution = ExecutionConfig(backend=self.traffic["backend"],
                                    scan=bool(self.traffic["scan"]),
                                    mesh=self.mesh,
                                    model_cfg=self.model_cfg)
        self.server = FederatedServer(
            self.network, None, params0, self.spans.wrap("input", sampler),
            self._server_cfg(seed=seed), execution=execution)
        # warm-up of every program the window runs
        self._run_segment(0, CHECK_ROUNDS, follow=False)

    def _run_segment(self, segment: int, rounds: int, follow: bool) -> None:
        """Run one segment through ``FederatedServer.run``; with
        ``follow``, keep its first ``CHECK_ROUNDS`` rounds on the host for
        the reference."""
        from repro.fl import RoundPlan

        with self.spans.span("plan"):
            plan = RoundPlan.connectivity_aware(
                self.network, self._server_cfg(seed=derive(self.seed, segment),
                                               t_max=rounds))
        span = Span(x0=_host(self._end)) if follow else None
        self._batches.clear()
        calls = [0]
        losses = []

        def hook(p):
            t = calls[0]
            calls[0] += 1
            out = self._eval(p)
            self._stamps.append(time.perf_counter())
            self._end = p
            losses.append(out["test_loss"])
            if span is not None and t < CHECK_ROUNDS:
                span.losses.append(out["test_loss"])
                if t in (0, CHECK_ROUNDS - 1):
                    setattr(span, "first" if t == 0 else "last", _host(p))
            return out

        with self.spans.span("segment"):
            history = self.server.run(eval_fn=hook, plan=plan)
        if span is not None:
            span.batches = [self._batches[t] for t in range(CHECK_ROUNDS)]
            span.rows = [plan[t] for t in range(CHECK_ROUNDS)]
            self.span = span
        self._batches.clear()
        self.plans.append(plan)
        self.histories.append(history)
        print(f"fl_lm_mesh: segment {segment}: {rounds} rounds, held-out "
              f"loss {losses[0]:.4f} -> {losses[-1]:.4f}", file=sys.stderr,
              flush=True)

    # -- window ----------------------------------------------------------

    def window(self, seconds: float) -> Window:
        self._stamps = []
        t0 = time.perf_counter()
        shortest = 0.0
        segment = 1
        with self.spans.span("window"):
            while True:
                start = time.perf_counter()
                last = start - t0 + shortest >= seconds
                self._run_segment(segment, self.traffic["segment_rounds"],
                                  follow=last)
                took = time.perf_counter() - start
                shortest = took if segment == 1 else min(shortest, took)
                segment += 1
                if last:
                    break
        stamps = list(self._stamps)
        return Window(t0=t0, t1=stamps[-1], stamps=stamps,
                      rounds=len(stamps))

    def release(self) -> None:
        """Free the program's device state before the reference runs; what
        the reference follows is on the host already."""
        self.server = None
        self._end = None
        self._eval = None
        self._batches = {}

    # -- costs -------------------------------------------------------------

    def train_flops_per_round(self) -> float:
        pop, tr = self.cfg["population"], self.cfg["training"]
        S = self.traffic["seq_len"]
        tokens = pop["n"] * tr["T"] * self.traffic["batch"] * S
        return train_flops_per_token(self.cfg["model"], S) * tokens

    def collective_bytes_per_round(self) -> float:
        """Cross-chip bytes a chip sends each round in ``fused_rs``: the
        reduce-scatter of the packed fp32 contribution row, then the
        all-gather of the aggregate row (P unpadded), each (W-1)/W of the
        row, as ``benchmarks/mixing_kernel.mesh_traffic_model`` counts
        the first."""
        W = self.cfg["population"]["n"]
        return 2.0 * (W - 1) / W * 4 * self.cfg["model"]["params"]

    def train_step_text(self) -> str:
        """The compiled text of the mesh train step the engine runs, at the
        cell's shapes (compiled again here, from the cache on a chip)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.fl.distributed import make_train_step
        from repro.models.model import Model

        if self._step_text is None:
            pop, tr = self.cfg["population"], self.cfg["training"]
            n = pop["n"]
            rep = NamedSharding(self.mesh, P())
            params = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=rep),
                jax.eval_shape(Model(self.model_cfg).init,
                               jax.random.key(0)))
            toks = jax.ShapeDtypeStruct(
                (n, tr["T"], self.traffic["batch"],
                 self.traffic["seq_len"] + 1), jnp.int32)
            f32 = jnp.float32
            step = make_train_step(self.model_cfg, self.mesh,
                                   mixing=self.traffic["backend"])
            shape = jax.ShapeDtypeStruct
            self._step_text = step.lower(
                params, toks, shape((n, n), f32), shape((n,), f32),
                shape((), f32), shape((), f32)).compile().as_text()
        return self._step_text

    # -- correctness -------------------------------------------------------

    def reference_run(self, reference, dtype: str = "float32", fault=None):
        span = self.span
        return reference.run_rounds(self.cfg["model"], span.x0,
                                    span.batches, span.rows, self.test_set,
                                    dtype=dtype, fault=fault)

    def numbers(self, reference, dtype: Optional[str] = None,
                fault=None) -> Dict[str, float]:
        """The numbers compared over the rounds followed: the program's
        (``dtype`` None), or those of the reference put in its place at
        ``dtype`` with ``fault``, each against the float32 reference."""
        if self._ref is None:
            self._ref = self.reference_run(reference)
        ref_params, ref_losses = self._ref
        span = self.span
        if dtype is None:
            first, last, losses = span.first, span.last, span.losses
        else:
            params, losses = self.reference_run(reference, dtype=dtype,
                                                fault=fault)
            first, last = params[0], params[-1]
        out = leaf_numbers(span.x0, first, last, losses, ref_params[0],
                           ref_params[-1], ref_losses)
        return {k: math.inf if math.isnan(v) else v for k, v in out.items()}

    def checks(self, reference, limits) -> List[Check]:
        out = [Check(k, v, limits[k])
               for k, v in self.numbers(reference).items() if k in limits]
        out.append(comm_check(self.plans, self.histories, reference.comm,
                              limits))
        return out


def _norms(x0, first, last, ref_first, ref_last):
    """Per leaf: |first - x0|, |ref_first - x0|, |last - x0|,
    |ref_last - x0| and |last - ref_last|, summed in float64 over chunks
    of the float32 host arrays."""
    out = {}
    for (path, a), b, c, rb, rc in zip(
            jax.tree_util.tree_leaves_with_path(x0),
            jax.tree.leaves(first), jax.tree.leaves(last),
            jax.tree.leaves(ref_first), jax.tree.leaves(ref_last)):
        arrays = [np.ravel(np.asarray(v, np.float32))
                  for v in (a, b, c, rb, rc)]
        sq = np.zeros(5)
        for lo in range(0, arrays[0].size, CHUNK):
            x, p1, pk, r1, rk = (v[lo:lo + CHUNK] for v in arrays)
            for i, d in enumerate((p1 - x, r1 - x, pk - x, rk - x,
                                   pk - rk)):
                d = d.astype(np.float64)
                sq[i] += float(np.dot(d, d))
        out[jax.tree_util.keystr(path)] = np.sqrt(sq)
    return out


def leaf_numbers(x0, first, last, losses, ref_first, ref_last,
                 ref_losses) -> Dict[str, float]:
    """``compare.training_numbers`` of a span, computed from each leaf's
    norms: the leaf's update and change of both runs, and the change's
    difference, stand in as points of a plane with the same lengths and
    distance, so the comparison reads the same numbers without copying
    the full trees again."""
    zero, pf, pl, rf, rl = {}, {}, {}, {}, {}
    for k, (nu, nr, nc, nq, ncq) in _norms(x0, first, last, ref_first,
                                           ref_last).items():
        zero[k] = np.zeros(2)
        pf[k], rf[k] = np.array([nu, 0.0]), np.array([nr, 0.0])
        if nc == 0:
            pl[k], rl[k] = np.zeros(2), np.array([nq, 0.0])
        else:
            a = (nc * nc + nq * nq - ncq * ncq) / (2 * nc)
            pl[k] = np.array([nc, 0.0])
            rl[k] = np.array([a, math.sqrt(max(nq * nq - a * a, 0.0))])
    return training_numbers(zero, [pf, pl], losses, [rf, rl], ref_losses)
