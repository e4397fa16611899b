#!/usr/bin/env python3
"""On-chip smoke test: the paper's FL round on one TPU, through the
entry points a user calls.

    python chip_smoke.py            # one chip: the Sec. 6 CNN round
    python chip_smoke.py --mesh4    # four chips: the mesh runtime only

One chip (default).  ``repro.launch.train`` runs the paper's Sec. 6
setting -- n=70 clients in c=7 k-regular D2D clusters, the full-width
CNN (~1.66M fp32 params), T=5 local steps, batch 32 -- for a few rounds
on ``LocalEngine`` with ``--backend aggregate --scan`` (one dispatch per
trajectory, the D2S aggregate in the compiled Pallas kernel), saving its
``RoundPlan``.  The same plan then replays on ``--backend einsum`` under
``jax.default_matmul_precision("highest")`` as the plain float32
reference.  The History comm columns (m, d2s, d2d) must match exactly,
every test loss must be finite, and the final test loss and accuracy
must agree within ``LOSS_RTOL`` / ``ACC_ATOL``.  Finally the aggregate
kernel runs at the run's packed shapes on seeded data and must match a
float64 NumPy aggregate within ``KERNEL_RTOL``, and its lowering must be
a Mosaic ``tpu_custom_call``, not the interpreter.

``--mesh4``.  ``MeshEngine`` on a (data=4, model=1) mesh -- four clients,
one per chip -- trains stablelm-1.6b at its published widths with
``n_layers`` cut to ``MESH4_LAYERS`` for a few connectivity-aware rounds
on the ``fused`` and ``fused_rs`` schedules, each compared with the
``einsum`` schedule on the same plan and tokens.

The last line of standard output is ``{"ok": true, "device": {...}}``;
it is printed only when every check passed.  Without a TPU the script
exits non-zero before any work.  Wall times printed on the way are
informational (they include compilation), not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# Sec. 6 of the paper: n=70, c=7, T=5, batch 32, the full CNN
PAPER_ARGS = ["--model", "cnn", "--n", "70", "--clusters", "7", "--T", "5",
              "--batch", "32", "--rounds", "3", "--seed", "0", "--quiet"]
# the reference differs from the kernel path by the local SGD's matmul
# precision (the TPU's default bf16 passes vs "highest") and the
# aggregate's summation order; both runs start from the same params and
# batches.  On a v5e the final test loss moved by ~5e-5 relative
LOSS_RTOL = 1e-3
ACC_ATOL = 0.01
# fp32 accumulation over n=70 rows: n * eps(fp32) ~ 4e-6 of sum |w_i x_i|
KERNEL_RTOL = 1e-5

MESH4_ARCH = "stablelm-1.6b"
# of 24: the compiled step of the largest of the three schedules needs
# 11.5 GiB per chip at 8 layers, 13.6 GiB at 12 and 18.5 GiB at 24 (a
# compile for a described v5e 2x2), and the initial params stay resident
# beside it on a 16 GB chip
MESH4_LAYERS = 8
MESH4_T, MESH4_B, MESH4_S = 2, 2, 256
MESH4_ROUNDS = 3
# bf16 params: one round's aggregate lands within a couple of bf16 ulps
MESH4_PARAM_RTOL = 2.0 ** -6
MESH4_LOSS_RTOL = 1e-2


def _fail(msg: str) -> int:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def _rows(path):
    with open(path) as f:
        return json.load(f)["rounds"]


def run_fl_round(out_dir: str, argv=PAPER_ARGS) -> list:
    """Kernel path + float32 einsum replay through ``repro.launch.train``.
    Returns the list of failed checks (empty on success)."""
    import jax

    from repro.launch import train

    plan = os.path.join(out_dir, "plan.json")
    main_out = os.path.join(out_dir, "aggregate_scan.json")
    ref_out = os.path.join(out_dir, "einsum_highest.json")

    t0 = time.perf_counter()
    if train.main(list(argv) + ["--backend", "aggregate", "--scan",
                                "--plan-out", plan, "--out", main_out]):
        return ["aggregate run exited non-zero"]
    t1 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        if train.main(list(argv) + ["--backend", "einsum", "--scan",
                                    "--plan", plan, "--out", ref_out]):
            return ["einsum reference exited non-zero"]
    t2 = time.perf_counter()
    got, ref = _rows(main_out), _rows(ref_out)
    print(f"info: aggregate+scan run {t1 - t0:.1f}s, einsum reference "
          f"{t2 - t1:.1f}s wall for {len(got)} rounds "
          "(data, compile and eval included; not a benchmark)", flush=True)

    problems = []
    comm = [(r["t"], r["m"], r["d2s"], r["d2d"]) for r in got]
    comm_ref = [(r["t"], r["m"], r["d2s"], r["d2d"]) for r in ref]
    print(f"comm columns (t, m, d2s, d2d): {comm}", flush=True)
    if comm != comm_ref:
        problems.append(f"comm columns differ: {comm} vs {comm_ref}")
    losses = [r["test_loss"] for r in got + ref]
    if not all(math.isfinite(x) for x in losses):
        problems.append(f"non-finite test loss: {losses}")
    for r, q in zip(got, ref):
        print(f"round {r['t']}: test_loss {r['test_loss']!r} vs "
              f"{q['test_loss']!r}  test_acc {r['test_acc']!r} vs "
              f"{q['test_acc']!r}", flush=True)
    loss, loss_ref = got[-1]["test_loss"], ref[-1]["test_loss"]
    rel = abs(loss - loss_ref) / max(abs(loss_ref), 1e-12)
    acc_diff = abs(got[-1]["test_acc"] - ref[-1]["test_acc"])
    print(f"final test loss rel diff {rel!r} (tol {LOSS_RTOL}), "
          f"test acc abs diff {acc_diff!r} (tol {ACC_ATOL})", flush=True)
    if not rel <= LOSS_RTOL:
        problems.append(f"final test loss {loss} vs reference {loss_ref}")
    if not acc_diff <= ACC_ATOL:
        problems.append(f"final test acc differs by {acc_diff}")
    return problems


def check_aggregate_kernel(n: int = 70) -> list:
    """Run the round's aggregate (``ops.aggregate_grouped``) at the shapes
    the CNN run packed -- ``n`` clients, one (n, P_pad) buffer per dtype
    group -- on seeded data, compare each row with a float64 NumPy
    aggregate, and check that the kernel lowered to Mosaic.  Returns the
    list of failed checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fl import ExecutionConfig
    from repro.fl.packing import pack_spec
    from repro.kernels.mixing.ops import aggregate_grouped
    from repro.models import cnn

    deltas = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((n,) + p.shape, p.dtype),
        cnn.init_cnn(0))
    spec = pack_spec(deltas)
    rng = np.random.default_rng(0)
    A = rng.random((n, n)) * (rng.random((n, n)) < 0.15) + np.eye(n)
    A = (A / A.sum(0, keepdims=True)).astype(np.float32)
    tau = (rng.random(n) < 0.5).astype(np.float32)
    m = np.float32(tau.sum())
    bufs = [rng.standard_normal((n, g.padded)).astype(g.dtype)
            for g in spec.groups]
    args = (jnp.asarray(A), jnp.asarray(tau), jnp.asarray(m),
            tuple(jnp.asarray(b) for b in bufs))
    chunk = ExecutionConfig().chunk

    problems = []
    text = aggregate_grouped.lower(*args, chunk=chunk).as_text()
    if "tpu_custom_call" not in text:
        problems.append("aggregate kernel did not lower to a Mosaic "
                        "tpu_custom_call")
    rows = aggregate_grouped(*args, chunk=chunk)
    w = (tau.astype(np.float64) @ A.astype(np.float64)) / float(m)
    for b, row in zip(bufs, rows):
        x = b.astype(np.float64)
        ref = w @ x
        scale = np.abs(w) @ np.abs(x)
        err = float(np.max(np.abs(np.asarray(row, np.float64) - ref)
                           / np.maximum(scale, 1e-30)))
        print(f"aggregate kernel {b.shape} {b.dtype}: max error "
              f"{err!r} of sum|w_i x_i| (tol {KERNEL_RTOL})", flush=True)
        if not err <= KERNEL_RTOL:
            problems.append(f"aggregate kernel error {err} on {b.shape}")
    return problems


def mesh4_phase(cfg=None, rounds: int = MESH4_ROUNDS,
                S: int = MESH4_S) -> list:
    """MeshEngine ``fused`` / ``fused_rs`` vs ``einsum`` on a (data=4,
    model=1) mesh; ``cfg`` defaults to the cut stablelm-1.6b.  Returns
    the list of failed checks."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import topology
    from repro.configs import get_config
    from repro.core.server import FederatedServer, ServerConfig
    from repro.fl import ExecutionConfig, RoundPlan
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import Model

    mesh = make_debug_mesh((4, 1))          # Auto axes, all four chips
    if cfg is None:
        cfg = dataclasses.replace(get_config(MESH4_ARCH),
                                  n_layers=MESH4_LAYERS)
    model = Model(cfg)
    n, T, B = 4, MESH4_T, MESH4_B
    print(f"mesh4: {cfg.name} n_layers={cfg.n_layers} d_model="
          f"{cfg.d_model} heads={cfg.n_heads} d_ff={cfg.d_ff} vocab="
          f"{cfg.vocab_size} on mesh {dict(mesh.shape)}", flush=True)

    network = topology.make_spec("k_regular", n=n, c=2, k_range=(1, 2),
                                 p_fail=0.0).build()
    scfg = ServerConfig(T=T, t_max=rounds, phi_max=1.0,
                        bound_kind="regular", seed=0, eta=lambda t: 1e-2)
    plan = RoundPlan.connectivity_aware(network, scfg)

    def sampler(rng, t):
        return jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        (n, T, B, S + 1)), jnp.int32)

    eval_toks = sampler(np.random.default_rng(123), 0)[0, 0]
    loss_fn = jax.jit(model.loss)

    def eval_fn(prm):
        return {"loss": float(loss_fn(prm, (eval_toks[:, :-1],
                                            eval_toks[:, 1:])))}

    def memory(tag):
        for d in mesh.devices.flat:
            stats = d.memory_stats() or {}
            print(f"mesh4 {tag} {d}: bytes_in_use="
                  f"{stats.get('bytes_in_use')} peak_bytes_in_use="
                  f"{stats.get('peak_bytes_in_use')} bytes_limit="
                  f"{stats.get('bytes_limit')}", flush=True)

    results = {}
    with jax.set_mesh(mesh):
        params0 = model.init(jax.random.key(0))
        print(f"mesh4: {model.param_count(params0):,} params", flush=True)
        for backend in ("einsum", "fused", "fused_rs"):
            execution = ExecutionConfig(backend=backend, mesh=mesh,
                                        model_cfg=cfg)
            server = FederatedServer(network, None, params0, sampler, scfg,
                                     algorithm="semidec",
                                     execution=execution)
            t0 = time.perf_counter()
            history = server.run(eval_fn=eval_fn, plan=plan)
            jax.block_until_ready(server.params)
            print(f"mesh4 {backend}: {time.perf_counter() - t0:.1f}s wall "
                  f"for {rounds} rounds (compile included; not a "
                  "benchmark)", flush=True)
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                    server.params):
                print(f"mesh4 {backend} leaf {jax.tree_util.keystr(path)} "
                      f"{leaf.shape} {leaf.dtype} sharding={leaf.sharding}",
                      flush=True)
            memory(backend)
            # keep only host copies: the chips hold one backend at a time
            results[backend] = (jax.tree.map(np.asarray, server.params),
                                history)
            del server

    problems = []
    ref_params, ref_hist = results["einsum"]
    ref_comm = [(r.m_actual, r.d2s, r.d2d) for r in ref_hist.records]
    ref_loss = [r.metrics["loss"] for r in ref_hist.records]
    print(f"mesh4 einsum comm (m, d2s, d2d) {ref_comm} loss {ref_loss}",
          flush=True)
    for backend in ("fused", "fused_rs"):
        params, hist = results[backend]
        comm = [(r.m_actual, r.d2s, r.d2d) for r in hist.records]
        loss = [r.metrics["loss"] for r in hist.records]
        worst = 0.0
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(ref_params)):
            a = a.astype(np.float32)
            b = b.astype(np.float32)
            excess = np.abs(a - b) - MESH4_PARAM_RTOL * np.abs(b)
            worst = max(worst, float(excess.max()))
        print(f"mesh4 {backend}: loss {loss}, params beyond "
              f"rtol {MESH4_PARAM_RTOL}: {worst!r}", flush=True)
        if comm != ref_comm:
            problems.append(f"{backend}: comm {comm} vs einsum {ref_comm}")
        if not all(math.isfinite(x) for x in loss + ref_loss):
            problems.append(f"{backend}: non-finite loss {loss}")
        elif any(abs(x - y) > MESH4_LOSS_RTOL * abs(y)
                 for x, y in zip(loss, ref_loss)):
            problems.append(f"{backend}: loss {loss} vs einsum {ref_loss}")
        if worst > 1e-6:
            problems.append(f"{backend}: params differ from einsum beyond "
                            f"rtol {MESH4_PARAM_RTOL}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the four-chip MeshEngine phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    print(f"jax {jax.__version__}; devices {devices}", flush=True)
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        return _fail(f"no TPU: jax found {dev.platform!r} devices")

    from repro.kernels.dispatch import default_interpret
    from repro.launch.cache import enable_compile_cache

    if default_interpret():
        return _fail("Pallas kernels would run in the interpreter "
                     "(REPRO_PALLAS_INTERPRET); unset it")
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.mesh4:
        if len(devices) < 4:
            return _fail(f"--mesh4 needs 4 chips, found {len(devices)}")
        problems = mesh4_phase()
    else:
        with tempfile.TemporaryDirectory() as out_dir:
            problems = run_fl_round(out_dir)
        problems += check_aggregate_kernel()
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
