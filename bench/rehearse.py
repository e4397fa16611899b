#!/usr/bin/env python3
"""Compile a cell's round at its real shapes for a described TPU v5e,
without a chip, and print what the compiler says it needs.

    JAX_PLATFORMS=cpu python bench/rehearse.py cnn70
    JAX_PLATFORMS=cpu python bench/rehearse.py lm [--arch stablelm-1.6b]
        [--layers 8] [--clients 4] [--T 5] [--batch 1] [--seq 2048,1024,512]

``cnn70`` compiles the paper's round (``core.rounds.make_round_fn``, the
``aggregate`` backend, Pallas compiled for Mosaic) on one described
chip.  ``lm`` compiles ``fl.distributed.make_train_step`` with the
``fused_rs`` schedule on a described v5e 2x2 as a (data=4, model=1)
mesh, one client per chip, for an architecture of the program's
``repro.configs`` cut to ``--layers`` layers and each sequence length, and reports
``memory_analysis()`` per chip: the arguments (the params and tokens),
the outputs (the new params) and the temporaries must fit beside each
other in the chip's 16 GB.  Nothing runs: these are the compiler's
figures, not measurements.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2.0 ** 30


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_gib"] = (out["argument_size_in_bytes"]
                        + out["output_size_in_bytes"]
                        + out["temp_size_in_bytes"]
                        - out["alias_size_in_bytes"]) / GIB
    return out


def _topology(name: str):
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def rehearse_cnn70() -> dict:
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import SingleDeviceSharding

    from bench.harness import cells
    from bench.systems.fl_cnn import init_params
    from repro.core.rounds import make_round_fn
    from repro.models import cnn

    cfg = cells.resolve("cnn70-paper").config
    model, pop, tr = cfg["model"], cfg["population"], cfg["training"]
    one = SingleDeviceSharding(_topology("v5e:2x2").devices[0])
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(partial(init_params, model=model),
                       jax.random.key(0)))
    n, T, B, hw = pop["n"], tr["T"], tr["batch"], model["image_hw"]
    batches = (sds((n, T, B, hw, hw, model["channels"])),
               sds((n, T, B), jnp.int32))
    loss = partial(cnn.l2_regularized_loss, cnn.cnn_apply, mu=model["l2_mu"])
    fn = make_round_fn(loss, mixing_backend="aggregate", interpret=False)
    t0 = time.perf_counter()
    compiled = fn.lower(params, batches, sds((n, n)), sds((n,)), sds(()),
                        sds(())).compile()
    out = _memory(compiled)
    out.update(cell="cnn70-paper", compile_s=time.perf_counter() - t0,
               mosaic="tpu_custom_call" in compiled.as_text())
    return out


def rehearse_lm(arch: str, layers: int, n: int, T: int, B: int,
                seqs) -> list:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.fl.distributed import make_train_step
    from repro.models.model import Model

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    topo = _topology("v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices).reshape(n, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(Model(cfg).init, jax.random.key(0)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    f32 = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=rep)
    out = []
    for S in seqs:
        step = make_train_step(cfg, mesh, mixing="fused_rs")
        toks = jax.ShapeDtypeStruct((n, T, B, S + 1), jnp.int32,
                                    sharding=NamedSharding(mesh, P("data")))
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            compiled = step.lower(params, toks, f32((n, n)), f32((n,)),
                                  f32(()), f32(())).compile()
        row = _memory(compiled)
        row.update(arch=arch, layers=layers, seq=S, params=n_params,
                   compile_s=time.perf_counter() - t0,
                   fits_16gb=row["total_gib"] * GIB <= 16e9)
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("cnn70", "lm"))
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--T", type=int, default=5)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seq", default="2048,1024,512")
    args = ap.parse_args(argv)
    if args.what == "cnn70":
        print(json.dumps(rehearse_cnn70()), flush=True)
    else:
        rehearse_lm(args.arch, args.layers, args.clients, args.T,
                    args.batch, [int(s) for s in args.seq.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
