"""Fused one-pass mix+aggregate path: kernel parity vs the composed
two-pass oracle (``mix_ref`` then eq.-4 update), packed-layout round
trips, backend-parity of the round function, and the scanned multi-round
driver's bitwise identity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.core import (D2DNetwork, FederatedServer, ServerConfig,
                        client_deltas, global_update, make_round_fn,
                        make_scanned_rounds, mix_deltas, network_matrix)
from repro.fl import packing
from repro.kernels.mixing.ops import (aggregate_grouped,
                                      mix_aggregate_grouped)
from repro.kernels.mixing.ref import mix_ref

jax.config.update("jax_enable_x64", False)


def mix_aggregate(A, tau, m, X, **kw):
    """One (n, p) buffer through the grouped entry point."""
    (mixed,), (agg,) = mix_aggregate_grouped(A, tau, m, (X,), **kw)
    return mixed, agg


def aggregate(A, tau, m, X, **kw):
    (agg,) = aggregate_grouped(A, tau, m, (X,), **kw)
    return agg


# ---------------------------------------------------------------------------
# oracle: the two-pass schedule the fused kernel replaces
# ---------------------------------------------------------------------------

def _two_pass(A, tau, m, X):
    """mix_ref (eq. 3) then the eq.-4 aggregate, fp32 accumulation."""
    mixed = mix_ref(A, X)
    agg = np.einsum("i,ip->p", np.asarray(tau, np.float32),
                    np.asarray(mixed, np.float32)) / float(m)
    return mixed, agg


def _check(n, p, dtype, seed, chunk=512):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((n, p)), dtype)
    tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    m = jnp.float32(max(1.0, float(tau.sum())))
    mixed, agg = mix_aggregate(A, tau, m, X, chunk=chunk)
    want_mixed, want_agg = _two_pass(A, tau, m, X)
    assert mixed.dtype == X.dtype
    assert agg.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(mixed, np.float32),
                               np.asarray(want_mixed, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(agg), want_agg,
                               rtol=tol, atol=tol)
    # aggregate-only variant: same row, no mixed output
    agg2 = aggregate(A, tau, m, X, chunk=chunk)
    np.testing.assert_allclose(np.asarray(agg2), want_agg,
                               rtol=tol, atol=tol)


@given(st.integers(2, 40), st.integers(1, 5000),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 2**31))
@settings(max_examples=25, deadline=None)
def test_fused_matches_two_pass_oracle(n, p, dtype, seed):
    _check(n, p, dtype, seed)


@pytest.mark.parametrize("n,p,dtype", [
    (7, 1000, jnp.float32),      # non-tile-aligned n and p
    (13, 4097, jnp.float32),     # p just past a lane multiple
    (3, 129, jnp.bfloat16),
    (8, 512, jnp.bfloat16),      # aligned shapes
    (1, 33, jnp.float32),        # single-client cluster
])
def test_fused_matches_two_pass_fixed_shapes(n, p, dtype):
    _check(n, p, dtype, seed=0)


def test_fused_identity_mixing_fedavg():
    """A = I (FedAvg): mixed == X and agg == mean of sampled rows."""
    rng = np.random.default_rng(3)
    n, p = 9, 700
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    tau = jnp.asarray([1, 0, 1, 1, 0, 1, 0, 0, 1], jnp.float32)
    m = jnp.float32(5.0)
    mixed, agg = mix_aggregate(jnp.eye(n), tau, m, X, chunk=256)
    np.testing.assert_allclose(np.asarray(mixed), np.asarray(X),
                               rtol=1e-6, atol=1e-6)
    want = np.asarray(X)[np.asarray(tau) > 0].sum(0) / 5.0
    np.testing.assert_allclose(np.asarray(agg), want, rtol=1e-5, atol=1e-5)


def test_fused_tau_all_zeros():
    """No client sampled: the aggregate row is exactly zero (m is clamped
    host-side; the kernel itself must produce 0, not NaN)."""
    rng = np.random.default_rng(4)
    n, p = 6, 300
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    X = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    _, agg = mix_aggregate(A, jnp.zeros(n), jnp.float32(1.0), X, chunk=256)
    np.testing.assert_array_equal(np.asarray(agg), np.zeros(p))


def test_fused_real_topology_preserves_sum():
    """Column-stochastic A + full sampling: agg == column mean of X."""
    rng = np.random.default_rng(5)
    net = D2DNetwork(n=20, c=2, p_fail=0.15)
    A = jnp.asarray(network_matrix(net.sample(rng), 20), jnp.float32)
    X = jnp.asarray(rng.standard_normal((20, 1025)), jnp.float32)
    _, agg = mix_aggregate(A, jnp.ones(20), jnp.float32(20.0), X, chunk=256)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(X).mean(0),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# packed delta layout
# ---------------------------------------------------------------------------

def _tree(rng, n, dtype=jnp.float32):
    return {"w": jnp.asarray(rng.standard_normal((n, 3, 5)), dtype),
            "b": jnp.asarray(rng.standard_normal((n, 7)), dtype),
            "scalarish": jnp.asarray(rng.standard_normal((n, 1)), dtype)}


def test_pack_unpack_round_trip():
    rng = np.random.default_rng(6)
    tree = _tree(rng, 11)
    spec = packing.pack_spec(tree)
    assert spec.n_groups == 1            # dtype-homogeneous: one buffer
    buf, = packing.pack(tree, spec)
    assert buf.shape == (11, spec.padded)
    assert spec.padded % 128 == 0 and spec.padded >= spec.total
    back = packing.unpack((buf,), spec)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def test_pack_single_dtype_bit_identical_to_one_buffer_layout():
    """A dtype-homogeneous tree must degenerate to the pre-grouping
    layout exactly: leaves concatenated in treedef order at their own
    dtype, zero-padded to the lane multiple."""
    rng = np.random.default_rng(60)
    n = 5
    tree = _tree(rng, n)
    spec = packing.pack_spec(tree)
    buf, = packing.pack(tree, spec)
    leaves = jax.tree.leaves(tree)
    legacy = np.concatenate(
        [np.asarray(l).reshape(n, -1) for l in leaves]
        + [np.zeros((n, spec.groups[0].pad), np.float32)], axis=1)
    assert buf.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(buf), legacy)


def test_pack_spec_is_cached_and_row_unpack_matches():
    rng = np.random.default_rng(7)
    t1, t2 = _tree(rng, 4), _tree(rng, 4)
    s1, s2 = packing.pack_spec(t1), packing.pack_spec(t2)
    assert s1 is s2                       # cached per (treedef, shapes, ...)
    row = jnp.arange(s1.total, dtype=jnp.float32)
    tree = packing.unpack_row(row, s1)
    assert tree["w"].shape == (3, 5) and tree["b"].shape == (7,)
    flat = np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(tree)])
    np.testing.assert_array_equal(flat, np.asarray(row))


@given(st.lists(st.integers(1, 40), min_size=1, max_size=6),
       st.integers(1, 9),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 2**31))
@settings(max_examples=25, deadline=None)
def test_pack_round_trip_property(sizes, n, dtype, seed):
    rng = np.random.default_rng(seed)
    tree = [jnp.asarray(rng.standard_normal((n, s)), dtype) for s in sizes]
    spec = packing.pack_spec(tree)
    back = packing.unpack(packing.pack(tree, spec), spec)
    for a, b in zip(tree, back):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(b, np.float32),
                                      np.asarray(a, np.float32))


def test_packed_mix_equals_leafwise_mix():
    """Mixing the packed buffer == leaf-wise mixing (linearity)."""
    rng = np.random.default_rng(8)
    n = 10
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    tree = _tree(rng, n)
    spec = packing.pack_spec(tree)
    buf, = packing.pack(tree, spec)
    got = packing.unpack(mix_ref(A, buf), spec)
    want = mix_deltas(A, tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# round-function backends + scanned driver
# ---------------------------------------------------------------------------

def quad_loss(params, batch):
    x = params["x"]
    b, = batch
    return 0.5 * jnp.sum((x - b.mean(axis=0)) ** 2)


def _round_inputs(rng, n, p, T, B, K):
    targets = rng.standard_normal((n, p))
    batches, As, taus, ms = [], [], [], []
    for _ in range(K):
        samp = targets[:, None, None, :] \
            + 0.05 * rng.standard_normal((n, T, B, p))
        batches.append((jnp.asarray(samp, jnp.float32),))
        As.append(jnp.asarray(rng.random((n, n)), jnp.float32))
        tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
        taus.append(tau)
        ms.append(jnp.float32(max(1.0, float(tau.sum()))))
    return batches, As, taus, ms


@pytest.mark.parametrize("backend", ["fused"])
def test_round_fn_backend_matches_einsum(backend):
    rng = np.random.default_rng(9)
    n, p, T, B, K = 6, 5, 3, 2, 3
    batches, As, taus, ms = _round_inputs(rng, n, p, T, B, K)
    eta = jnp.float32(0.1)
    params = {"x": jnp.zeros(p)}

    ref_fn = make_round_fn(quad_loss)
    got_fn = make_round_fn(quad_loss, mixing_backend=backend, chunk=256)
    ref_p, got_p = params, params
    for t in range(K):
        ref_p, ref_mixed = ref_fn(ref_p, batches[t], As[t], taus[t],
                                  ms[t], eta)
        got_p, got_mixed = got_fn(got_p, batches[t], As[t], taus[t],
                                  ms[t], eta)
        np.testing.assert_allclose(np.asarray(got_mixed["x"]),
                                   np.asarray(ref_mixed["x"]),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_p["x"]),
                               np.asarray(ref_p["x"]),
                               rtol=1e-5, atol=1e-6)


def test_scanned_rounds_bitwise_identical_to_sequential():
    rng = np.random.default_rng(10)
    n, p, T, B, K = 5, 4, 3, 2, 4
    batches, As, taus, ms = _round_inputs(rng, n, p, T, B, K)
    etas = [jnp.float32(0.2 / (1 + t)) for t in range(K)]
    params = {"x": jnp.zeros(p)}

    round_fn = make_round_fn(quad_loss)
    seq = []
    prm = params
    for t in range(K):
        prm, _ = round_fn(prm, batches[t], As[t], taus[t], ms[t], etas[t])
        seq.append(np.asarray(prm["x"]))

    scanned = make_scanned_rounds(quad_loss, K)
    batches_seq = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    final, params_seq = scanned(params, batches_seq, jnp.stack(As),
                                jnp.stack(taus), jnp.stack(ms),
                                jnp.stack(etas))
    # bitwise: the scan body is the same composition as round_fn
    np.testing.assert_array_equal(np.asarray(final["x"]), seq[-1])
    for t in range(K):
        np.testing.assert_array_equal(np.asarray(params_seq["x"][t]), seq[t])


def _server_pair(scan_rounds, mixing_backend="einsum",
                 record_mixed=False):
    rng = np.random.default_rng(11)
    n, c, p, T = 12, 2, 4, 3
    targets = rng.standard_normal((n, p)).astype(np.float32)

    def sampler(r, t):
        samp = targets[:, None, None, :] \
            + 0.05 * r.standard_normal((n, T, 2, p))
        return (jnp.asarray(samp, jnp.float32),)

    net = D2DNetwork(n=n, c=c, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=T, t_max=5, phi_max=0.3, seed=3,
                       eta=lambda t: 0.2 / (1 + 0.3 * t))
    server = FederatedServer(net, quad_loss, {"x": jnp.zeros(p)}, sampler,
                             cfg, algorithm="semidec",
                             mixing_backend=mixing_backend,
                             scan_rounds=scan_rounds,
                             record_mixed=record_mixed)
    x_star = targets.mean(axis=0)
    hist = server.run(eval_fn=lambda prm: {
        "gap": float(jnp.sum((prm["x"] - x_star) ** 2))})
    return server, hist


def test_server_scan_rounds_matches_sequential_history():
    """Opt-in scan driver: identical History (records, ledger, metrics)
    and identical final params -- semantics unchanged."""
    s_seq, h_seq = _server_pair(scan_rounds=False)
    s_scan, h_scan = _server_pair(scan_rounds=True)
    assert len(h_seq.records) == len(h_scan.records)
    for a, b in zip(h_seq.records, h_scan.records):
        assert (a.t, a.m, a.m_actual, a.d2s, a.d2d) == \
            (b.t, b.m, b.m_actual, b.d2s, b.d2d)
        assert a.metrics["gap"] == pytest.approx(b.metrics["gap"],
                                                 rel=1e-6, abs=1e-7)
    np.testing.assert_array_equal(np.asarray(s_seq.params["x"]),
                                  np.asarray(s_scan.params["x"]))
    assert h_scan.ledger.cumulative_cost()[-1] == \
        h_seq.ledger.cumulative_cost()[-1]


def test_server_fused_backend_converges():
    _, hist = _server_pair(scan_rounds=False, mixing_backend="fused")
    gaps = hist.series("gap")
    assert gaps[-1] < gaps[0]


def test_make_round_fn_rejects_unknown_backend():
    with pytest.raises(ValueError):
        make_round_fn(quad_loss, mixing_backend="nope")


# ---------------------------------------------------------------------------
# aggregate-only round variant (ROADMAP: server rounds that never record
# per-client mixed deltas dispatch kernels.mixing.ops.aggregate_grouped)
# ---------------------------------------------------------------------------

def test_round_fn_aggregate_matches_einsum_and_returns_no_mixed():
    rng = np.random.default_rng(12)
    n, p, T, B, K = 6, 5, 3, 2, 3
    batches, As, taus, ms = _round_inputs(rng, n, p, T, B, K)
    eta = jnp.float32(0.1)
    ref_fn = make_round_fn(quad_loss)
    agg_fn = make_round_fn(quad_loss, mixing_backend="aggregate", chunk=256)
    ref_p = agg_p = {"x": jnp.zeros(p)}
    for t in range(K):
        ref_p, _ = ref_fn(ref_p, batches[t], As[t], taus[t], ms[t], eta)
        agg_p, mixed = agg_fn(agg_p, batches[t], As[t], taus[t], ms[t], eta)
        assert mixed is None          # never materialized
    np.testing.assert_allclose(np.asarray(agg_p["x"]),
                               np.asarray(ref_p["x"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("requested,recorded,effective", [
    ("fused", False, "aggregate"),
    ("fused", True, "fused"),
    ("einsum", False, "einsum"),
])
def test_server_backend_dispatch(requested, recorded, effective):
    rng = np.random.default_rng(13)
    net = D2DNetwork(n=12, c=2, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=2, t_max=1, seed=0)
    server = FederatedServer(
        net, quad_loss, {"x": jnp.zeros(4)},
        lambda r, t: (jnp.asarray(r.standard_normal((12, 2, 2, 4)),
                                  jnp.float32),),
        cfg, algorithm="semidec", mixing_backend=requested,
        record_mixed=recorded)
    assert server.effective_backend == effective


def test_server_aggregate_history_matches_two_pass():
    """Regression pin for the aggregate-only dispatch: History must be
    record-for-record equivalent to the two-pass (record_mixed=True)
    path -- same plans, ledger, and metrics up to f32 reduction order."""
    _, h_two = _server_pair(scan_rounds=False, mixing_backend="fused",
                            record_mixed=True)
    _, h_agg = _server_pair(scan_rounds=False, mixing_backend="fused",
                            record_mixed=False)
    assert len(h_two.records) == len(h_agg.records)
    for a, b in zip(h_two.records, h_agg.records):
        assert (a.t, a.m, a.m_actual, a.d2s, a.d2d, a.eta,
                a.psi_bound) == (b.t, b.m, b.m_actual, b.d2s, b.d2d,
                                 b.eta, b.psi_bound)
        assert a.metrics["gap"] == pytest.approx(b.metrics["gap"],
                                                 rel=1e-5, abs=1e-6)
    np.testing.assert_array_equal(h_two.ledger.cumulative_cost(),
                                  h_agg.ledger.cumulative_cost())


def test_server_aggregate_scan_rounds_compose():
    """scan_rounds + the aggregate-only backend: one dispatch, same
    History semantics."""
    s_seq, h_seq = _server_pair(scan_rounds=False, mixing_backend="fused")
    s_scan, h_scan = _server_pair(scan_rounds=True, mixing_backend="fused")
    assert s_seq.effective_backend == s_scan.effective_backend == "aggregate"
    np.testing.assert_array_equal(np.asarray(s_seq.params["x"]),
                                  np.asarray(s_scan.params["x"]))
    for a, b in zip(h_seq.records, h_scan.records):
        assert a.metrics["gap"] == pytest.approx(b.metrics["gap"],
                                                 rel=1e-6, abs=1e-7)


# ---------------------------------------------------------------------------
# per-dtype buffer groups: payload bytes, round trips, kernel parity
# ---------------------------------------------------------------------------

def _mixed_tree(rng, n):
    """bf16-majority LM-style tree with a small fp32 tail."""
    tree = {f"bf16_{i}": jnp.asarray(rng.standard_normal((n, 1000)),
                                     jnp.bfloat16) for i in range(3)}
    tree["fp32_bias"] = jnp.asarray(rng.standard_normal((n, 16)),
                                    jnp.float32)
    return tree


def test_pack_mixed_dtype_does_not_promote_payload_bytes():
    """Regression pin (former ROADMAP xfail): per-dtype groups keep a
    bf16-majority payload at bf16 width -- total packed bytes stay near
    the ideal byte count and under 0.6x what the promoted-fp32 one-buffer
    layout would ship."""
    rng = np.random.default_rng(14)
    n = 4
    tree = _mixed_tree(rng, n)
    spec = packing.pack_spec(tree)
    bufs = packing.pack(tree, spec)
    nbytes = sum(b.nbytes for b in bufs)
    assert nbytes == spec.nbytes(n)
    ideal = sum(np.prod(l.shape) * l.dtype.itemsize
                for l in jax.tree.leaves(tree))
    assert nbytes <= 1.25 * ideal
    # the promoted layout packs every leaf at result_type (fp32) width
    assert packing.promoted_nbytes(spec, n) == n * 3072 * 4
    assert nbytes < 0.6 * packing.promoted_nbytes(spec, n)


def test_pack_mixed_dtype_groups_layout():
    """Leaves partition by dtype in first-seen treedef order; each group
    is lane-aligned at its own width."""
    rng = np.random.default_rng(140)
    tree = _mixed_tree(rng, 4)
    spec = packing.pack_spec(tree)
    assert spec.n_groups == 2
    g_bf16, g_fp32 = spec.groups
    assert g_bf16.dtype == jnp.bfloat16 and g_fp32.dtype == jnp.float32
    assert g_bf16.leaf_ids == (0, 1, 2) and g_fp32.leaf_ids == (3,)
    for g in spec.groups:
        assert g.padded % 128 == 0 and g.padded >= g.total
    bufs = packing.pack(tree, spec)
    assert [b.dtype for b in bufs] == [jnp.bfloat16, jnp.float32]


def test_pack_mixed_dtype_round_trip_stays_exact():
    """Per-dtype groups: unpack must restore per-leaf dtypes and values
    exactly, with no cross-dtype casting anywhere."""
    rng = np.random.default_rng(15)
    n = 3
    tree = {"a": jnp.asarray(rng.standard_normal((n, 40)), jnp.bfloat16),
            "b": jnp.asarray(rng.standard_normal((n, 7)), jnp.float32)}
    spec = packing.pack_spec(tree)
    assert spec.n_groups == 2                 # no result_type promotion
    back = packing.unpack(packing.pack(tree, spec), spec)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(tree[k], np.float32))


@given(st.integers(1, 5), st.integers(0, 5), st.integers(1, 6),
       st.integers(1, 8), st.integers(0, 2**31))
@settings(max_examples=25, deadline=None)
def test_pack_grouped_round_trip_property(n_bf16, n_fp32, n, shards, seed):
    """Grouped round trip over random interleaved mixed-dtype trees,
    including fused_rs-style shard-aligned padding per group."""
    rng = np.random.default_rng(seed)
    leaves = [(jnp.bfloat16 if i < n_bf16 else jnp.float32,
               int(rng.integers(1, 300))) for i in range(n_bf16 + n_fp32)]
    rng.shuffle(leaves)
    tree = [jnp.asarray(rng.standard_normal((n, s)), dt)
            for dt, s in leaves]
    spec = packing.pack_spec(tree, shards=shards)
    for g in spec.groups:
        assert g.padded % (128 * shards) == 0
        assert (g.padded // shards) % 128 == 0
    bufs = packing.pack(tree, spec)
    assert len(bufs) == spec.n_groups
    back = packing.unpack(bufs, spec)
    for a, b in zip(tree, back):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(b, np.float32),
                                      np.asarray(a, np.float32))
    # per-group aggregate rows (fp32, padded width) unpack exactly too
    rows = tuple(jnp.arange(g.padded, dtype=jnp.float32)
                 for g in spec.groups)
    agg_leaves = jax.tree.leaves(packing.unpack_row(rows, spec))
    for g, row in zip(spec.groups, rows):
        for i, o, s in zip(g.leaf_ids, g.offsets, g.sizes):
            np.testing.assert_array_equal(
                np.asarray(agg_leaves[i]).ravel(),
                np.asarray(row)[o:o + s])


def test_grouped_kernel_launch_matches_leafwise_oracle():
    """One fused launch per dtype group == leaf-wise eq. 3 + eq. 4."""
    rng = np.random.default_rng(141)
    n = 6
    tree = _mixed_tree(rng, n)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    m = jnp.float32(max(1.0, float(tau.sum())))
    spec = packing.pack_spec(tree)
    bufs = packing.pack(tree, spec)

    mixed_bufs, rows = mix_aggregate_grouped(A, tau, m, bufs, chunk=256)
    assert [b.dtype for b in mixed_bufs] == [b.dtype for b in bufs]
    assert all(r.dtype == jnp.float32 for r in rows)
    mixed = packing.unpack(mixed_bufs, spec)
    want_mixed = mix_deltas(A, tree)
    agg = packing.unpack_row(rows, spec)
    w = (np.asarray(tau, np.float32) @ np.asarray(A, np.float32)) / float(m)
    for k in tree:
        tol = 5e-2 if tree[k].dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(mixed[k], np.float32),
                                   np.asarray(want_mixed[k], np.float32),
                                   rtol=tol, atol=tol)
        want_agg = w @ np.asarray(tree[k], np.float32)
        np.testing.assert_allclose(np.asarray(agg[k]), want_agg,
                                   rtol=tol, atol=tol)
    # aggregate-only grouped variant: identical rows
    rows2 = aggregate_grouped(A, tau, m, bufs, chunk=256)
    for r1, r2 in zip(rows, rows2):
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                                   rtol=1e-6, atol=1e-6)


def test_round_fn_fused_handles_mixed_dtype_params():
    """End-to-end: a mixed bf16/fp32 param tree through the 'fused' and
    'aggregate' backends matches the einsum oracle."""
    def loss(params, batch):
        b, = batch
        return 0.5 * jnp.sum(
            (params["x"].astype(jnp.float32) - b.mean(axis=0)) ** 2) \
            + 0.5 * jnp.sum((params["y"] - 1.0) ** 2)

    rng = np.random.default_rng(142)
    n, p, T, B = 6, 8, 2, 2
    batches = (jnp.asarray(rng.standard_normal((n, T, B, p)), jnp.float32),)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    tau = jnp.asarray([1, 0, 1, 1, 0, 1], jnp.float32)
    m = jnp.float32(4.0)
    eta = jnp.float32(0.1)
    params = {"x": jnp.zeros(p, jnp.bfloat16), "y": jnp.zeros(3)}

    ref_p, _ = make_round_fn(loss)(params, batches, A, tau, m, eta)
    for backend in ("fused", "aggregate"):
        got_p, _ = make_round_fn(loss, mixing_backend=backend, chunk=256)(
            params, batches, A, tau, m, eta)
        for k in params:
            assert got_p[k].dtype == params[k].dtype
            np.testing.assert_allclose(np.asarray(got_p[k], np.float32),
                                       np.asarray(ref_p[k], np.float32),
                                       rtol=1e-2, atol=1e-2)


def test_pack_rejects_mismatched_tree():
    """pack() must refuse a tree that doesn't match the spec instead of
    silently scrambling the layout."""
    rng = np.random.default_rng(143)
    tree = _tree(rng, 4)
    spec = packing.pack_spec(tree)
    with pytest.raises(ValueError, match="does not match the spec"):
        packing.pack({"w": tree["w"]}, spec)          # missing leaves
    swapped = {"w": tree["b"], "b": tree["w"], "scalarish":
               tree["scalarish"]}
    with pytest.raises(ValueError, match="trailing shape"):
        packing.pack(swapped, spec)                   # right treedef,
                                                      # wrong leaf shapes
    retyped = {k: (v.astype(jnp.bfloat16) if k == "b" else v)
               for k, v in tree.items()}
    with pytest.raises(ValueError, match="dtype"):
        packing.pack(retyped, spec)                   # wrong leaf dtype
    with pytest.raises(ValueError, match="unpack"):
        packing.unpack(packing.pack(tree, spec) * 2, spec)


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_pack_shard_aligned_round_trip(shards):
    rng = np.random.default_rng(16)
    tree = _tree(rng, 6)
    spec = packing.pack_spec(tree, shards=shards)
    assert spec.padded % (128 * shards) == 0
    assert (spec.padded // shards) % 128 == 0   # per-shard lane alignment
    buf, = packing.pack(tree, spec)
    assert buf.shape == (6, spec.padded)
    back = packing.unpack(buf, spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    # aggregate-row unpack ignores the shard padding as well
    row = jnp.arange(spec.padded, dtype=jnp.float32)
    flat = np.concatenate([np.asarray(l).ravel()
                           for l in jax.tree.leaves(
                               packing.unpack_row(row, spec))])
    np.testing.assert_array_equal(flat, np.asarray(row)[:spec.total])


def test_pack_spec_cache_distinguishes_shards():
    rng = np.random.default_rng(17)
    tree = _tree(rng, 5)
    s1 = packing.pack_spec(tree)
    s2 = packing.pack_spec(tree, shards=4)
    assert s1 is not s2 and s2 is packing.pack_spec(tree, shards=4)
    assert s2.padded >= s1.padded


@given(st.lists(st.integers(1, 60), min_size=1, max_size=5),
       st.integers(1, 8), st.integers(1, 6), st.integers(0, 2**31))
@settings(max_examples=25, deadline=None)
def test_pack_shard_aligned_round_trip_property(sizes, shards, n, seed):
    rng = np.random.default_rng(seed)
    tree = [jnp.asarray(rng.standard_normal((n, s)), jnp.float32)
            for s in sizes]
    spec = packing.pack_spec(tree, shards=shards)
    assert spec.padded % (128 * shards) == 0
    back = packing.unpack(packing.pack(tree, spec), spec)
    for a, b in zip(tree, back):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))


def test_pack_spec_rejects_bad_shards():
    rng = np.random.default_rng(18)
    with pytest.raises(ValueError):
        packing.pack_spec(_tree(rng, 2), shards=0)


def test_server_rejects_contradictory_record_mixed():
    net = D2DNetwork(n=12, c=2, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=2, t_max=1, seed=0)
    with pytest.raises(ValueError, match="record_mixed"):
        FederatedServer(net, quad_loss, {"x": jnp.zeros(4)},
                        lambda r, t: None, cfg, algorithm="semidec",
                        mixing_backend="aggregate", record_mixed=True)
