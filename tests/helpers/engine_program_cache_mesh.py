"""Subprocess helper: ``MeshEngine`` builds its train step once.

Four clients, one a device, on a (data=4, model=1) mesh of forced host
CPU devices, with the ``fused_rs`` schedule and a tiny StableLM config.
One engine runs two plans of different K: the step is built once, the
second ``execute`` counts a program-cache hit and traces, lowers and
compiles nothing, and the params are bitwise those of two fresh engines.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4; prints one
JSON line.
"""

import json
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro import spans                                         # noqa: E402
from repro.configs import get_config                            # noqa: E402
from repro.core import D2DNetwork, ServerConfig                 # noqa: E402
from repro.fl import ExecutionConfig, RoundPlan, make_engine    # noqa: E402
from repro.fl import engine as engine_module                    # noqa: E402
from repro.launch.mesh import make_debug_mesh                   # noqa: E402
from repro.models.model import Model                            # noqa: E402

N, T, B, S, VOCAB = 4, 2, 1, 8, 128


def _plan(seed, K):
    net = D2DNetwork(n=N, c=2, k_range=(1, 1), p_fail=0.0)
    return RoundPlan.connectivity_aware(net, ServerConfig(
        T=T, t_max=K, phi_max=0.5, seed=seed,
        eta=lambda t: 0.05 / (1 + t)))


def _tokens(seed, K):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, VOCAB, size=(N, T, B, S + 1)),
                        jnp.int32) for _ in range(K)]


def main():
    mesh = make_debug_mesh((N, 1), axes=("data", "model"))
    cfg = get_config("stablelm-1.6b", reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "vocab_size": VOCAB,
                           "name": "tiny-4dev"})
    params0 = Model(cfg).init(jax.random.key(0))
    execution = ExecutionConfig(backend="fused_rs", mesh=mesh, model_cfg=cfg)

    builds = [0]
    real = engine_module.make_train_step

    def counted(*a, **k):
        builds[0] += 1
        return real(*a, **k)

    engine_module.make_train_step = counted
    plans = [_plan(1, 2), _plan(2, 3)]
    data = [_tokens(1, 2), _tokens(2, 3)]

    engine = make_engine(execution)
    first, second = spans.Recorder(), spans.Recorder()
    with spans.recording(first):
        params, _ = engine.execute(plans[0], params0, data[0])
        jax.block_until_ready(params)
    with spans.recording(second):
        params, _ = engine.execute(plans[1], params, data[1])
        jax.block_until_ready(params)
    one_engine = builds[0]

    fresh = params0
    for plan, tokens in zip(plans, data):
        fresh, _ = make_engine(execution).execute(plan, fresh, tokens)
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(params),
                               jax.tree.leaves(fresh)))
    print(json.dumps({
        "devices": len(jax.devices()),
        "builds_one_engine": one_engine,
        "counters": {key: int(c[1]) for rec in (first, second)
                     for (name, key), c in rec.counters.items()
                     if name == "engine.program_cache"},
        "second_execute_compiles": sorted(
            event for event, _ in second.counters
            if event in spans.COMPILE_EVENTS),
        "builds_fresh_engines": builds[0] - one_engine,
        "bitwise_as_fresh_engines": same,
    }), flush=True)


if __name__ == "__main__":
    main()
