"""Benchmark: fused one-pass mix+aggregate vs the two-pass schedule.

Correctness (allclose across shapes/dtypes), wall time on this host
(interpret mode on CPU; the kernels' BlockSpec tiling targets TPU VMEM),
and a bytes-moved model of per-round HBM traffic.  Payload sizes bracket
the paper's CNN (1.66M params) and per-leaf LM deltas.

Traffic model (payload (n, p), element size B; A and the tau row are
kilobytes and ignored):

  two-pass   read X (npB) + write mixed (npB) + re-read mixed (npB)
             + write agg (pB)                          ~ 3 npB + pB
  fused      read X ONCE (npB) + write mixed (npB) + write agg (pB)
                                                       ~ 2 npB + pB
  agg-only   read X ONCE (npB) + write agg (pB)        ~  npB + pB

i.e. the fused kernel reads the payload once per round where the
two-pass schedule reads it twice (X, then mixed) -- a ~2x reduction in
payload reads and ~1.5x in total traffic; the aggregate-only variant
(FedAvg A=I, or rounds that don't log per-client deltas) is ~3x.

Cross-worker traffic on the mesh runtime (``mesh_traffic_model``): the
per-leaf psum schedule all-reduces every worker's tau-weighted delta
contribution leaf by leaf -- each worker RECEIVES the full fp32 row, so
per-worker bytes are ``2 (W-1)/W * 4p`` over ``L`` collective launches.
The worker-sharded 'fused_rs' path reduce-scatters the single packed row
instead: each worker receives only its ``p/W`` column shard,
``(W-1)/W * 4p`` bytes in ONE collective -- exactly half the cross-worker
traffic and 1/L-th the launches (the re-replication is deferred to the
next round's broadcast, which the train step performs anyway).

Per-dtype payload bytes (``grouped_payload_rows``): the dtype-grouped
packed layout is MEASURED against the promoted one-buffer layout it
replaced -- a bf16-majority tree ships ~0.5x the promoted bytes --
and the numbers land in BENCH_mixing.json, where the CI baseline check
pins them against regression.

Sparse vs dense (``sparse_vs_dense_rows``): ELL gather / segment-sum
mixing against the dense kernels on real block-diagonal topology
matrices -- the A-operand footprint drops from O(n^2) to O(n d_max)
(the ``bytes_A_*`` fields are informational, not baseline-gated).

Plan overhead (``plan_overhead_rows``): host-side cost of the
declarative trajectory object -- building a K-round
``RoundPlan.connectivity_aware`` (Algorithm 1's rule, all topology
sampling included) plus its JSON round-trip.  Establishes that planning
is microseconds-per-round host work, never on the device critical path,
and sizes the pinned-trajectory artifacts ``benchmarks.run --plan``
replays.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl import packing
from repro.kernels.mixing.ops import (aggregate, aggregate_grouped,
                                      aggregate_grouped_q, mix,
                                      mix_aggregate, sparse_aggregate,
                                      sparse_mix)
from repro.kernels.mixing.ref import mix_ref

__all__ = ["run", "traffic_model", "mesh_traffic_model",
           "grouped_payload_rows", "quant_payload_rows",
           "plan_overhead_rows", "sparse_vs_dense_rows"]

# launch count for the per-leaf psum schedule in the reported model: a
# representative LM delta-tree leaf count (the packed fused_rs schedule
# always launches once, whatever the tree shape)
_LM_LEAVES = 50


def traffic_model(n: int, p: int, itemsize: int) -> dict:
    """Bytes moved per round for each schedule (payload terms only)."""
    npB = n * p * itemsize
    pB = p * 4                      # fp32 aggregate row
    return dict(
        bytes_two_pass=3 * npB + pB,
        bytes_fused=2 * npB + pB,
        bytes_agg_only=npB + pB,
        payload_reads_two_pass=2,
        payload_reads_fused=1,
        traffic_ratio_fused=(3 * npB + pB) / (2 * npB + pB),
        traffic_ratio_agg_only=(3 * npB + pB) / (npB + pB),
    )


def mesh_traffic_model(n_workers: int, p: int, n_leaves: int = 1) -> dict:
    """Cross-worker bytes per round for the mesh D2S aggregation.

    Bandwidth-optimal ring collectives over a (p,) fp32 contribution row:
    an all-reduce (the per-leaf psum schedule) moves ``2 (W-1)/W``
    payloads per worker across ``n_leaves`` launches; a reduce-scatter
    (the packed 'fused_rs' schedule) moves ``(W-1)/W`` in one launch.
    """
    full = p * 4                               # fp32 contribution row
    frac = (n_workers - 1) / n_workers
    psum = 2.0 * frac * full
    rs = frac * full
    return dict(
        mesh_workers=n_workers,
        bytes_psum_per_worker=psum,
        bytes_reduce_scatter_per_worker=rs,
        collective_launches_psum=n_leaves,
        collective_launches_fused_rs=1,
        cross_worker_ratio=psum / rs if rs else float("inf"),
    )


def _time(fn, reps=3):
    fn()  # warm (compile / trace)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def grouped_payload_rows(quiet: bool = False):
    """MEASURED per-dtype payload bytes: the dtype-grouped packed layout
    (``repro.fl.packing``) vs the promoted one-buffer layout it replaced.

    The promoted layout packs every leaf at ``jnp.result_type`` of the
    tree -- fp32 whenever any leaf is fp32 -- so a bf16-majority LM tree
    ships ~2x its ideal bytes.  Grouping packs each dtype at native
    width; these rows pin the measured ratio in BENCH_mixing.json (and
    the CI baseline check fails if the packed bytes ever regress).
    """
    rng = np.random.default_rng(1)
    rows = []
    # (label, n, bf16 trailing cols per leaf x leaves, fp32 cols x leaves)
    for label, n, bf16_shape, fp32_shape in (
            ("bf16-majority-lm", 16, (65_536, 4), (1_024, 2)),
            ("bf16-only", 16, (65_536, 4), (0, 0)),
            ("fp32-cnn", 70, (0, 0), (23_713, 2))):
        tree = {}
        for i in range(bf16_shape[1]):
            tree[f"w{i}"] = jnp.asarray(
                rng.standard_normal((n, bf16_shape[0])), jnp.bfloat16)
        for i in range(fp32_shape[1]):
            tree[f"b{i}"] = jnp.asarray(
                rng.standard_normal((n, fp32_shape[0])), jnp.float32)
        spec = packing.pack_spec(tree)
        bufs = packing.pack(tree, spec)
        measured = sum(b.nbytes for b in bufs)
        assert measured == spec.nbytes(n)
        ideal = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in jax.tree.leaves(tree))
        # the one-buffer layout this replaced: every leaf at result_type
        promoted = packing.promoted_nbytes(spec, n)

        A = jnp.eye(n, dtype=jnp.float32)
        tau = jnp.ones(n, jnp.float32)
        m = jnp.float32(n)
        t_agg = _time(lambda: aggregate_grouped(A, tau, m, bufs))
        row = dict(kind="grouped_payload", layout=label, n=n,
                   n_groups=spec.n_groups,
                   group_dtypes=[str(jnp.dtype(g.dtype)) for g in
                                 spec.groups],
                   bytes_grouped=int(measured), bytes_promoted=int(promoted),
                   bytes_ideal=int(ideal),
                   grouped_over_ideal=measured / ideal,
                   promoted_over_grouped=promoted / measured,
                   us_agg_grouped_interp=t_agg,
                   kernel_launches=spec.n_groups)
        rows.append(row)
        if not quiet:
            print(f"{label:18s} n={n:3d} groups={spec.n_groups} "
                  f"grouped={measured/1e6:7.3f}MB "
                  f"promoted={promoted/1e6:7.3f}MB "
                  f"(x{promoted/measured:.2f} saved) "
                  f"ideal-overhead x{measured/ideal:.3f} "
                  f"agg={t_agg:9.1f}us/{spec.n_groups} launches")
    return rows


def quant_payload_rows(quiet: bool = False):
    """MEASURED compressed wire bytes: quantized payload groups
    (``QuantSpec`` storage + per-block fp32 absmax scales) vs the
    full-precision grouped layout they ride on.

    ``bytes_quantized`` counts everything that crosses the wire -- the
    stored containers (int8 / nibble-packed int4 / fp8) PLUS the fp32
    scale side buffers -- so the ratio is honest end-to-end compression,
    not container-only.  The two gate rows (int4 on the bf16-majority LM
    tree, int8 on the fp32 CNN tree) must land at <= 0.3x the grouped
    bytes; BENCH_mixing.json pins them via the CI baseline check.
    Parity: the fused dequant-epilogue aggregate kernel is checked
    against the einsum oracle over the dequantized rows before timing.
    """
    from repro.fl.packing import QuantSpec

    rng = np.random.default_rng(2)
    rows = []
    # (layout label, n, bf16 cols x leaves, fp32 cols x leaves, storage)
    for label, n, bf16_shape, fp32_shape, storage in (
            ("bf16-majority-lm", 16, (65_536, 4), (1_024, 2), "int8"),
            ("bf16-majority-lm", 16, (65_536, 4), (1_024, 2), "int4"),
            ("fp32-cnn", 70, (0, 0), (23_713, 2), "int8")):
        tree = {}
        for i in range(bf16_shape[1]):
            tree[f"w{i}"] = jnp.asarray(
                rng.standard_normal((n, bf16_shape[0])), jnp.bfloat16)
        for i in range(fp32_shape[1]):
            tree[f"b{i}"] = jnp.asarray(
                rng.standard_normal((n, fp32_shape[0])), jnp.float32)
        quant = QuantSpec(storage=storage, block=512)
        spec = packing.pack_spec(tree)            # full-precision wire
        qspec = packing.pack_spec(tree, quant=quant)
        bufs = packing.pack(tree, qspec)
        stored, scales, _ = packing.quantize_packed(bufs, qspec)
        measured = (sum(b.nbytes for b in stored)
                    + sum(s.nbytes for s in scales))
        assert measured == qspec.quantized_nbytes(n)
        grouped = spec.nbytes(n)
        ratio = measured / grouped

        # parity: fused dequant-epilogue kernel vs the dequantized oracle
        A = jnp.eye(n, dtype=jnp.float32)
        tau = jnp.ones(n, jnp.float32)
        m = jnp.float32(n)
        dq = packing.dequantize_packed(stored, scales, qspec)
        got = aggregate_grouped_q(A, tau, m, stored, scales, quant=quant)
        for g, d in zip(got, dq):
            ref = np.einsum("i,ip->p", np.asarray(tau),
                            np.asarray(d, np.float32)) / float(n)
            np.testing.assert_allclose(np.asarray(g), ref,
                                       rtol=1e-5, atol=1e-5)
        t_agg = _time(lambda: aggregate_grouped_q(A, tau, m, stored,
                                                  scales, quant=quant))

        row = dict(kind="quant_payload", layout=label, n=n,
                   storage=storage, block=quant.block,
                   n_groups=qspec.n_groups,
                   bytes_grouped=int(grouped),
                   bytes_quantized=int(measured),
                   bytes_scales=int(qspec.scales_nbytes(n)),
                   ratio_vs_grouped=ratio,
                   us_agg_quant_interp=t_agg,
                   kernel_launches=qspec.n_groups)
        rows.append(row)
        if not quiet:
            print(f"{label:18s} n={n:3d} {storage:4s} block={quant.block} "
                  f"grouped={grouped/1e6:7.3f}MB "
                  f"quantized={measured/1e6:7.3f}MB "
                  f"(x{ratio:.3f}, scales {qspec.scales_nbytes(n)/1e3:.1f}KB) "
                  f"agg={t_agg:9.1f}us")
    return rows


def plan_overhead_rows(quiet: bool = False):
    """Host-side RoundPlan cost: build (Algorithm 1 planning incl. all
    topology/sampling draws), ``to_json``, and ``from_json`` wall time,
    plus the serialized artifact size.  Pure host numpy -- no device
    work -- so these are wall-clock rows, not baseline-gated fields."""
    from repro.core.graphs import D2DNetwork
    from repro.core.server import ServerConfig
    from repro.fl.plan import RoundPlan

    rows = []
    for n, c, K in ((70, 7, 30),       # the paper's Sec. 6 scale
                    (128, 8, 20)):
        net = D2DNetwork(n=n, c=c, k_range=(6, 9), p_fail=0.1)
        cfg = ServerConfig(t_max=K, phi_max=0.06, seed=0)

        t0 = time.perf_counter()
        plan = RoundPlan.connectivity_aware(net, cfg)
        t_build = (time.perf_counter() - t0) * 1e6

        t0 = time.perf_counter()
        js = plan.to_json()
        t_dump = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        back = RoundPlan.from_json(js)
        t_load = (time.perf_counter() - t0) * 1e6
        assert back.allclose(plan)

        rows.append(dict(kind="plan_overhead", n=n, clusters=c, rounds=K,
                         us_build=t_build, us_build_per_round=t_build / K,
                         us_to_json=t_dump, us_from_json=t_load,
                         plan_json_bytes=len(js)))
        if not quiet:
            print(f"plan n={n:4d} c={c} K={K:3d}  "
                  f"build={t_build:9.1f}us ({t_build / K:7.1f}us/round)  "
                  f"to_json={t_dump:9.1f}us  from_json={t_load:9.1f}us  "
                  f"json={len(js) / 1e6:.2f}MB")
    return rows


def sparse_vs_dense_rows(quiet: bool = False):
    """Sparse (ELL gather / segment-sum) vs dense mixing on real
    block-diagonal topology matrices.

    The A-operand bytes are the story: a cluster topology's equal-
    neighbor matrix stores ``n * d_max`` entries in ELL form (int32
    index + fp32 weight) against the dense ``n^2`` fp32 layout, so the
    operand footprint scales O(n) instead of O(n^2) -- the ratio below
    is n/(2 d_max) and grows without bound.  Wall times are CPU times
    (the Pallas kernels in interpret mode, the ELL mix an XLA gather)
    and NOT baseline-gated (the new ``bytes_A_*`` fields are
    informational, outside ``_BYTE_FIELDS``, so the committed gate is
    untouched).
    """
    from repro import topology
    from repro.core.adjacency import network_matrix, network_matrix_sparse

    rows = []
    for n, c, p in ((256, 32, 8_192), (1_024, 128, 2_048)):
        model = topology.make_spec("k_regular", n=n, c=c).build()
        rng = np.random.default_rng(0)
        clusters = model.sample_sparse(rng, 0)
        sp = network_matrix_sparse(clusters, n)
        idx_np, w_np = sp.ell()
        idx, w = jnp.asarray(idx_np), jnp.asarray(w_np)
        A = jnp.asarray(network_matrix(
            [g.dense() for g in clusters], n), jnp.float32)
        np.testing.assert_array_equal(np.asarray(sp.dense()),
                                      np.asarray(A))

        rng2 = np.random.default_rng(1)
        X = jnp.asarray(rng2.standard_normal((n, p)), jnp.float32)
        tau = jnp.asarray(rng2.integers(0, 2, n), jnp.float32)
        m = jnp.float32(max(1.0, float(tau.sum())))

        np.testing.assert_allclose(np.asarray(sparse_mix(idx, w, X)),
                                   np.asarray(mix(A, X)),
                                   rtol=1e-4, atol=1e-4)

        t_dense_mix = _time(lambda: mix(A, X))
        t_sparse_mix = _time(lambda: sparse_mix(idx, w, X))
        t_dense_agg = _time(lambda: aggregate(A, tau, m, X))
        t_sparse_agg = _time(lambda: sparse_aggregate(idx, w, tau, m, X))

        d_max = int(idx_np.shape[1])
        bytes_dense = n * n * 4
        bytes_ell = n * d_max * (4 + 4)
        row = dict(kind="sparse_vs_dense", n=n, clusters=c, p=p,
                   nnz=int(sp.nnz), d_max=d_max,
                   bytes_A_dense=bytes_dense, bytes_A_ell=bytes_ell,
                   A_operand_ratio=bytes_dense / bytes_ell,
                   us_mix_dense_interp=t_dense_mix,
                   us_mix_sparse_interp=t_sparse_mix,
                   us_agg_dense_interp=t_dense_agg,
                   us_agg_sparse_interp=t_sparse_agg)
        rows.append(row)
        if not quiet:
            print(f"n={n:5d} c={c:4d} p={p:6d} d_max={d_max:2d} "
                  f"A: dense={bytes_dense/1e6:8.3f}MB "
                  f"ell={bytes_ell/1e6:8.3f}MB "
                  f"(x{bytes_dense/bytes_ell:6.1f})  "
                  f"mix {t_dense_mix:9.1f}us->{t_sparse_mix:9.1f}us  "
                  f"agg {t_dense_agg:9.1f}us->{t_sparse_agg:9.1f}us")
    return rows


def run(quiet: bool = False):
    rng = np.random.default_rng(0)
    rows = []
    # interpret-mode (CPU) payloads; the kernels' BlockSpec tiling targets
    # TPU VMEM where the paper's full 1.66M-param CNN payload applies.
    for n, p, dtype in ((70, 32_768, jnp.float32),
                        (70, 8_192, jnp.float32),
                        (16, 65_536, jnp.bfloat16),
                        (32, 16_384, jnp.bfloat16)):
        A = jnp.asarray(rng.random((n, n)) * (rng.random((n, n)) < 0.3),
                        jnp.float32)
        A = A / jnp.clip(A.sum(axis=0, keepdims=True), 1e-6)  # col-stochastic
        X = jnp.asarray(rng.standard_normal((n, p)), dtype)
        tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
        m = jnp.float32(max(1.0, float(tau.sum())))

        # -- correctness: fused vs the composed two-pass oracle
        ref_mixed = mix_ref(A, X)
        ref_agg = np.einsum("i,ip->p", np.asarray(tau, np.float32),
                            np.asarray(ref_mixed, np.float32)) / float(m)
        got_mixed, got_agg = mix_aggregate(A, tau, m, X)
        atol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(got_mixed, np.float32),
                                   np.asarray(ref_mixed, np.float32),
                                   rtol=atol, atol=atol)
        np.testing.assert_allclose(np.asarray(got_agg), ref_agg,
                                   rtol=atol, atol=atol)

        # -- wall time (interpret mode): two-pass vs fused vs agg-only
        # (jitted like the fused wrapper, so the comparison is end-to-end
        # schedule vs schedule, not jit-dispatch overhead)
        @jax.jit
        def two_pass(A=A, X=X, tau=tau, m=m):
            mixed = mix(A, X)
            return jnp.einsum("i,ip->p", tau,
                              mixed.astype(jnp.float32),
                              preferred_element_type=jnp.float32) / m

        t_ref = _time(lambda: mix_ref(A, X))
        t_two = _time(two_pass)
        t_fused = _time(lambda: mix_aggregate(A, tau, m, X))
        t_agg = _time(lambda: aggregate(A, tau, m, X))

        model = traffic_model(n, p, np.dtype(dtype).itemsize)
        # cross-worker model: 8 workers (the CPU test mesh) moving this
        # row's p columns; _LM_LEAVES launches for the per-leaf schedule
        mesh = mesh_traffic_model(8, p, n_leaves=_LM_LEAVES)
        rows.append(dict(n=n, p=p, dtype=str(np.dtype(dtype).name),
                         us_ref=t_ref, us_two_pass_interp=t_two,
                         us_fused_interp=t_fused, us_agg_only_interp=t_agg,
                         match=True, **model, **mesh))
        if not quiet:
            print(f"n={n:3d} p={p:8d} {np.dtype(dtype).name:9s} "
                  f"ref={t_ref:9.1f}us two-pass={t_two:9.1f}us "
                  f"fused={t_fused:9.1f}us agg-only={t_agg:9.1f}us "
                  f"traffic x{model['traffic_ratio_fused']:.2f} "
                  f"(agg-only x{model['traffic_ratio_agg_only']:.2f})  OK")

    if not quiet:
        print("\ncross-worker D2S bytes/worker (fp32 row, ring "
              "collectives): per-leaf psum vs packed fused_rs "
              "reduce-scatter")
        for W in (8, 256):
            m = mesh_traffic_model(W, 1_660_000, n_leaves=_LM_LEAVES)
            print(f"  W={W:4d} p=1.66M  psum={m['bytes_psum_per_worker']/1e6:7.2f}MB"
                  f" x{m['collective_launches_psum']} launches   "
                  f"fused_rs={m['bytes_reduce_scatter_per_worker']/1e6:7.2f}MB"
                  f" x1 launch   ratio x{m['cross_worker_ratio']:.2f}")
        print("\nper-dtype grouped packing: measured payload bytes vs the "
              "promoted one-buffer layout")
    rows.extend(grouped_payload_rows(quiet=quiet))
    if not quiet:
        print("\nquantized payload groups: compressed wire bytes vs the "
              "full-precision grouped layout")
    rows.extend(quant_payload_rows(quiet=quiet))
    if not quiet:
        print("\nsparse vs dense mixing on block-diagonal topology "
              "matrices (ELL A-operand bytes vs the (n, n) layout)")
    rows.extend(sparse_vs_dense_rows(quiet=quiet))
    if not quiet:
        print("\nhost-side RoundPlan overhead (build + JSON round-trip)")
    rows.extend(plan_overhead_rows(quiet=quiet))
    return rows


if __name__ == "__main__":
    run()
