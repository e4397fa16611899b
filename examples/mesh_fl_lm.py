"""Production-path demo: semi-decentralized FL of a transformer LM on a
multi-pod mesh (emulated with 8 host devices).

This is the same code path the 512-chip dry-run proves out, executed for
real at toy scale: a reduced qwen2-style LM, clients = (pod, data) mesh
indices, ring D2D mixing over the intra-pod axis, connectivity-aware m(t)
from the sampled cluster topology each round -- all driven by the
declarative plan/engine API: the trajectory is ONE ``RoundPlan`` (built
by Algorithm 1's rule, optionally with straggler dropout) and the mesh
runtime is ONE ``ExecutionConfig``.

    PYTHONPATH=src python examples/mesh_fl_lm.py [--rounds 3]
        [--dropout 0.25] [--plan-out plan.json]
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse                                                 # noqa: E402
from dataclasses import replace                                 # noqa: E402

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.configs import get_config                            # noqa: E402
from repro.core.graphs import D2DNetwork                        # noqa: E402
from repro.core.server import FederatedServer, ServerConfig     # noqa: E402
from repro.data.synthetic import make_token_stream              # noqa: E402
from repro.data.loader import lm_batches                        # noqa: E402
from repro.fl import ExecutionConfig, RoundPlan                 # noqa: E402
from repro.launch.mesh import make_debug_mesh                   # noqa: E402
from repro.models.model import Model                            # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--T", type=int, default=2)
    ap.add_argument("--phi-max", type=float, default=1.0)
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-round client straggler probability")
    ap.add_argument("--plan-out", default="",
                    help="save the executed RoundPlan as JSON")
    args = ap.parse_args()

    mesh = make_debug_mesh((2, 2, 2))          # (pod, data, model)
    n, c = 4, 2                                # clients, clusters (= pods)

    cfg = replace(get_config("qwen2-7b", reduced=True), vocab_size=256)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    print(f"model: {cfg.name}  params={model.param_count(params):,}")

    # the whole run is two declarative objects: the trajectory plan
    # (Algorithm 1's connectivity-aware rule, plus optional stragglers)
    # and the runtime selection (mesh + ring D2D mixing).
    network = D2DNetwork(n=n, c=c, k_range=(1, 2), p_fail=0.0)
    scfg = ServerConfig(T=args.T, t_max=args.rounds, phi_max=args.phi_max,
                        bound_kind="regular", seed=0,
                        eta=lambda t: 0.05)
    plan = RoundPlan.connectivity_aware(network, scfg)
    if args.dropout > 0:
        plan = plan.with_dropout(args.dropout, np.random.default_rng(1))
    execution = ExecutionConfig(backend="ring", mesh=mesh, model_cfg=cfg)

    B, S = 2, 32
    stream = make_token_stream(n_tokens=1 << 15, vocab=cfg.vocab_size,
                               seed=0)

    def sampler(rng, t):
        """Per-round (n, T, B, S+1) token minibatches from the stream."""
        xs, ys = lm_batches(stream, rng, n, args.T, B, S)
        toks = np.zeros((n, args.T, B, S + 1), np.int32)
        toks[..., :-1] = np.asarray(xs)
        toks[..., -1] = np.asarray(ys)[..., -1]   # next-token continuation
        return jnp.asarray(toks)

    def eval_fn(prm):
        toks = sampler(np.random.default_rng(123), 0)
        return {"loss": float(model.loss(prm, (toks[0, 0, :, :-1],
                                               toks[0, 0, :, 1:])))}

    server = FederatedServer(network, None, params, sampler, scfg,
                             algorithm="semidec", execution=execution)
    with jax.set_mesh(mesh):
        history = server.run(eval_fn=eval_fn, plan=plan)

    for rec in history.records:
        print(f"round {rec.t}: m(t)={rec.m_actual}/{n}  d2s={rec.d2s}  "
              f"loss={rec.metrics['loss']:.4f}")
    if args.plan_out:
        server.last_plan.save(args.plan_out)
        print(f"trajectory pinned to {args.plan_out} "
              "(re-run it with server.run(plan=RoundPlan.load(path)))")

    # serve the trained model: prefill + greedy decode
    prompt = jnp.asarray(np.asarray(stream[:16])[None], jnp.int32)
    out = model.generate(server.params, prompt, n_new=8)
    print("generated:", np.asarray(out)[0].tolist())


if __name__ == "__main__":
    main()
