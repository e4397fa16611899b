"""System under test: the paper's FL round with four DeepSeek-V2-Lite
clients on one chip, driven through ``FederatedServer.run(plan=...)`` on
``LocalEngine`` (``ExecutionConfig(backend="aggregate")``).  The chip
holds each client's share of one pipeline stage (its held experts and
vocabulary slice); at this size the engine trains the clients one at a
time (the 'sequential' cohort form) and adds each client's weighted
delta to the round's aggregate as it finishes.

Set-up first checks that the program's model is the published block
(MLA, the gates, YaRN, the held experts), and raises if not.  The rest
is as ``fl_lm_mesh``, whose segments, window, host copies of the rounds
the reference follows, and comparison this system reuses: one
``FederatedServer`` (weights made on the chip from the seed in one
jitted call), a first segment of ``CHECK_ROUNDS`` rounds that compiles
every program the window uses, then segments of ``segment_rounds``
rounds, each with a plan built from the seed and the segment index.

Every round ends with the held-out loss, the mean of each of
``eval_sequences`` 2048-token sequences' loss: the round's sync.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.systems import fl_lm_mesh
from bench.systems.fl_cnn import derive

CHECK_ROUNDS = fl_lm_mesh.CHECK_ROUNDS


def _published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The program config's keys that state the published block, as the
    configuration's file gives them."""
    y = cfg["rope_scaling"]
    return {
        "family": "moe", "mla": True, "norm_type": "rms",
        "mlp_type": ("swiglu" if cfg["hidden_act"] == "silu"
                     else cfg["hidden_act"]),
        "q_lora_rank": cfg["q_lora_rank"] or 0,
        "norm_topk_prob": cfg["norm_topk_prob"],
        "routed_scaling": float(cfg["routed_scaling_factor"]),
        "rope_theta": float(cfg["rope_theta"]),
        "yarn_factor": float(y["factor"]),
        "yarn_original_max_pos": y["original_max_position_embeddings"],
        "yarn_beta_fast": float(y["beta_fast"]),
        "yarn_beta_slow": float(y["beta_slow"]),
        "yarn_mscale": float(y["mscale"]),
        "yarn_mscale_all_dim": float(y["mscale_all_dim"]),
        "first_dense_layers": cfg["first_k_dense_replace"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "qkv_bias": cfg["attention_bias"],
        "router_aux_weight": float(cfg["aux_loss_alpha"])}


def model_config(cfg: Dict[str, Any]):
    """The program's config for the configuration's model: its registry
    entry at the configuration's widths, depth, held experts, vocabulary
    and params dtype.  Raises if the program's block is not the
    published one."""
    from repro.configs import get_config
    from repro.models.model import Model

    base = get_config(cfg["arch"])
    if cfg["scoring_func"] != "softmax" or cfg["topk_method"] != "greedy" \
            or cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError("the configuration is not a softmax, greedy "
                         "top-k, YaRN model")
    wrong = {k: (getattr(base, k), v) for k, v in _published(cfg).items()
             if getattr(base, k) != v}
    if wrong:
        raise ValueError(f"the program's {cfg['arch']} is not the published "
                         f"block (program, published): {wrong}")
    H = cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        nope_head_dim=cfg["qk_nope_head_dim"],
        rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], n_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        moe_d_ff=cfg["moe_intermediate_size"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], norm_eps=cfg["rms_norm_eps"],
        vocab_size=cfg["vocab_size"], dtype=cfg["params_dtype"],
        moe_impl="ragged", remat=True)
    shapes = jax.eval_shape(Model(mc).init, jax.random.key(0))
    moe = shapes["decoder"]["layers"]["moe"]
    held, router = moe["gate"].shape[1], moe["router"].shape[-1]
    if (held, router) != (cfg["n_routed_experts"], cfg["router_experts"]) \
            or "wq" not in shapes["decoder"]["layers"]["mla"]:
        raise ValueError(f"the program's {cfg['arch']} params hold "
                         f"{held} experts under a router of {router}, or "
                         "have a q LoRA: not the published share")
    return mc


def layer_params(m: Dict[str, Any]) -> Dict[str, float]:
    """Matmul params of one token's pass, by part: MLA's projections, the
    dense SwiGLU, and per MoE layer the router, the shared experts and
    the held experts at the share of a token's top-k that falls on them
    (k x held / experts)."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    r, ff = m["kv_lora_rank"], m["moe_intermediate_size"]
    expert = 3 * d * ff
    return {
        "mla": d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
        + H * dv * d,
        "dense_mlp": 3 * d * m["intermediate_size"],
        "router": d * m["router_experts"],
        "shared": m["n_shared_experts"] * expert,
        "held_experts": expert * m["num_experts_per_tok"]
        * m["n_routed_experts"] / m["router_experts"],
        "head": d * m["vocab_size"]}


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward and backward FLOPs of one trained token: 6 per matmul
    parameter it passes through (``layer_params``; the embedding is a
    gather) and attention's 6 S H (dn + dr + dv) a layer; recomputation
    not counted."""
    p = layer_params(m)
    L, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    matmul = (L * p["mla"] + dense * p["dense_mlp"]
              + (L - dense) * (p["router"] + p["shared"]
                               + p["held_experts"]) + p["head"])
    attn = 6.0 * seq * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return 6.0 * matmul + attn * L


class System(fl_lm_mesh.System):
    """``devices``: the chip the cell runs on (the first)."""

    def __init__(self, cell, seed: int, spans, devices):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.spans = spans
        self.model_cfg = model_config(self.cfg)
        self.device = list(devices)[0]
        self._stamps: List[float] = []
        self.plans: List[Any] = []
        self.histories: List[Any] = []
        self.span: Optional[fl_lm_mesh.Span] = None
        self._ref = None
        self._step_text: Optional[str] = None

    # -- set-up ----------------------------------------------------------

    def setup(self) -> None:
        from jax.sharding import SingleDeviceSharding

        from repro import topology
        from repro.core.server import FederatedServer, ServerConfig
        from repro.data import lm_batches, make_token_stream
        from repro.fl import ExecutionConfig
        from repro.models.model import Model

        cfg, pop, tr = self.cfg, self.cfg["population"], self.cfg["training"]
        n, S, B = pop["n"], self.traffic["seq_len"], self.traffic["batch"]
        E = self.traffic["eval_sequences"]
        seed = self.seed
        # one stream; the clients' regions first, the held-out tail last
        stream = make_token_stream(pop["train_tokens"] + E * (S + 1),
                                   vocab=cfg["vocab_size"],
                                   order=pop["markov_order"],
                                   seed=derive(seed, 1))
        train = stream[:pop["train_tokens"]]
        self.test_set = stream[pop["train_tokens"]:].reshape(E, S + 1)
        self._batches: Dict[int, np.ndarray] = {}

        def sampler(rng, t):
            xs, ys = lm_batches(train, rng, n, tr["T"], B, S)
            xs, ys = np.asarray(xs), np.asarray(ys)
            self._batches[t] = np.concatenate([xs, ys[..., -1:]], axis=-1)
            return jax.device_put((xs, ys), self.device)

        model = Model(self.model_cfg)
        key = jax.random.wrap_key_data(np.asarray(
            np.random.SeedSequence([seed, 7]).generate_state(2), np.uint32))
        params0 = jax.jit(model.init, out_shardings=SingleDeviceSharding(
            self.device))(key)
        self._end = params0

        tests = jax.device_put(self.test_set, self.device)

        @jax.jit
        def test_loss(p, tests):
            return jnp.mean(jax.lax.map(
                lambda w: model.loss(p, (w[None, :-1], w[None, 1:])), tests))

        def eval_fn(p):
            return {"test_loss": float(test_loss(p, tests))}

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
                                    scan=bool(self.traffic["scan"]))
        self.server = FederatedServer(
            self.network, model.loss, params0,
            self.spans.wrap("input", sampler), self._server_cfg(seed=seed),
            execution=execution)
        # warm-up of every program the window runs
        self._run_segment(0, CHECK_ROUNDS, follow=False)

    # -- costs -------------------------------------------------------------

    def train_flops_per_round(self) -> float:
        pop, tr = self.cfg["population"], self.cfg["training"]
        S = self.traffic["seq_len"]
        tokens = pop["n"] * tr["T"] * self.traffic["batch"] * S
        return train_flops_per_token(self.cfg, S) * tokens

    def train_step_text(self) -> str:
        """The compiled text of the round program in which the engine's
        clients train, at the cell's shapes and in the cohort form the
        engine picks here (compiled again, from the cache on a chip)."""
        from repro.core.rounds import make_round_fn
        from repro.fl import ExecutionConfig, resolve_backend
        from repro.fl.engine import _bytes_limit, cohort_form
        from repro.models.model import Model

        if self._step_text is None:
            pop, tr = self.cfg["population"], self.cfg["training"]
            n = pop["n"]
            model = Model(self.model_cfg)
            params = jax.eval_shape(model.init, jax.random.key(0))
            execution = ExecutionConfig(backend=self.traffic["backend"])
            round_fn = make_round_fn(
                model.loss, mixing_backend=resolve_backend(execution),
                chunk=execution.chunk, interpret=execution.interpret,
                cohort=cohort_form(params, n, _bytes_limit()))
            # the sequential round's clients train in its first program
            round_fn = getattr(round_fn, "accumulate", round_fn)
            shape = jax.ShapeDtypeStruct
            toks = shape((n, tr["T"], self.traffic["batch"],
                          self.traffic["seq_len"]), jnp.int32)
            f32 = jnp.float32
            self._step_text = round_fn.lower(
                params, (toks, toks), shape((n, n), f32), shape((n,), f32),
                shape((), f32), shape((), f32)).compile().as_text()
        return self._step_text

    # -- correctness -------------------------------------------------------

    def reference_run(self, reference, dtype: str = "float32", fault=None):
        span = self.span
        return reference.run_rounds(self.cfg, span.x0, span.batches,
                                    span.rows, self.test_set, dtype=dtype,
                                    fault=fault)
