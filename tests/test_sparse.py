"""Sparse representations and kernels (sparse-ClusterGraph tentpole).

Covers the containers (``SparseClusterGraph``, ``SparseA``,
``SparseAseq``), the O(nnz) equal-neighbor assembly
(``network_matrix_sparse`` vs the dense ``network_matrix`` oracle), the
``sample_sparse`` topology path (every family, dense == densified
sparse, identical rng streams), the ELL Pallas kernels vs the dense
kernels, and the satellite regressions that ride along:

* ``KRegular`` degree clamp at tiny cluster sizes with
  ``self_loops=False`` (a union of shift permutations has only ``s - 1``
  non-self targets);
* the ``self_loops=False`` policy surviving the
  ``ensure_positive_out_degree`` repair in every family;
* the shared ``m == 0`` safe-divide in ``combine_weights``, dense and
  ELL.

Kernel parity is allclose, not bitwise: the unrolled ELL gather loop
accumulates in neighbor order while the dense MXU matmul reduces over
all n; both accumulate in fp32, so at these sizes 1e-5 absolute is a
generous bound on the reordering error.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro import topology
from repro.core.adjacency import network_matrix, network_matrix_sparse
from repro.core.graphs import (ClusterGraph, SparseClusterGraph,
                               degree_stats, degree_stats_from_arrays,
                               ensure_positive_out_degree)
from repro.core.metrics import count_d2d_transmissions
from repro.core.sparse import SparseA, SparseAseq, ell_from_dense
from repro.kernels.mixing import ops

ALL_FAMILIES = topology.families()


def _random_A(rng, n, max_deg=4):
    """A random sparse nonnegative matrix with >= 1 entry per row."""
    A = np.zeros((n, n), np.float32)
    for i in range(n):
        nbrs = rng.choice(n, size=rng.integers(1, max_deg + 1),
                          replace=False)
        A[i, nbrs] = rng.random(len(nbrs)).astype(np.float32) + 0.1
    return A


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------


def test_sparse_cluster_graph_round_trip_and_stats():
    rng = np.random.default_rng(0)
    W = (rng.random((7, 7)) < 0.4).astype(np.int8)
    np.fill_diagonal(W, 1)
    verts = np.arange(10, 17)
    g = SparseClusterGraph.from_dense(verts, W)
    assert g.size == 7
    assert np.array_equal(g.dense().W, W)
    assert np.array_equal(g.W, W)
    assert np.array_equal(g.d_out, W.sum(axis=1))
    assert np.array_equal(g.d_in, W.sum(axis=0))
    assert g.d2d_transmissions == count_d2d_transmissions(W)
    # degree-only stats match the dense densify-then-count path
    assert g.stats == degree_stats(W)


def test_degree_stats_from_arrays_rejects_dead_rows():
    with pytest.raises(ValueError):
        degree_stats_from_arrays(np.array([2, 0, 1]), np.array([1, 1, 1]))


def test_sparse_a_round_trips_and_ell_padding():
    rng = np.random.default_rng(1)
    A = _random_A(rng, 9)
    sp = SparseA.from_dense(A)
    assert sp.nnz == (A != 0).sum()
    assert np.array_equal(sp.dense(), A)
    idx, w = sp.ell()
    assert idx.shape == w.shape == (9, int(sp.row_degrees.max()))
    # ELL reconstructs the matrix: scatter each slot back
    back = np.zeros_like(A)
    for i in range(9):
        for k in range(idx.shape[1]):
            back[i, idx[i, k]] += w[i, k]
    assert np.allclose(back, A)
    # padding slots are index 0 / weight 0.0 (the no-op convention)
    deg = sp.row_degrees
    for i in range(9):
        assert (w[i, deg[i]:] == 0.0).all()
        assert (idx[i, deg[i]:] == 0).all()
    # edge-list assembly canonicalizes to the same CSR
    dst, src = np.nonzero(A)
    perm = rng.permutation(len(dst))
    again = SparseA.from_edges(9, dst[perm], src[perm],
                               A[dst, src][perm])
    assert again.equals(sp)
    ei, ew = ell_from_dense(A)
    assert np.array_equal(ei, idx) and np.array_equal(ew, w)


def test_sparse_a_identity_is_fedavg_matrix():
    sp = SparseA.identity(5)
    assert sp.nnz == 5
    assert np.array_equal(sp.dense(), np.eye(5, dtype=np.float32))


def test_sparse_aseq_surface_and_shared_dmax():
    rng = np.random.default_rng(2)
    A_t = np.stack([_random_A(rng, 6, max_deg=k + 1) for k in range(3)])
    seq = SparseAseq.from_dense(A_t)
    assert seq.shape == (3, 6, 6)
    assert len(seq) == 3
    assert np.array_equal(seq.dense(), A_t)
    assert isinstance(seq[1], SparseA)
    sub = seq[1:]
    assert isinstance(sub, SparseAseq) and len(sub) == 2
    idx, w = seq.ell()
    # one shared d_max across rounds (scan shape stability)
    assert idx.shape == w.shape == (3, 6, seq.max_degree)


# ---------------------------------------------------------------------------
# equal-neighbor assembly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
def test_network_matrix_sparse_matches_dense(family):
    n, c = 24, 3
    model = topology.make_spec(family, n=n, c=c).build()
    rng = np.random.default_rng(7)
    clusters = model.sample_sparse(rng, 0)
    A_sp = network_matrix_sparse(clusters, n)
    A_dn = network_matrix([g.dense() for g in clusters], n)
    assert np.allclose(A_sp.dense(), A_dn, atol=1e-7)


def test_network_matrix_sparse_rejects_dead_out_degree():
    g = SparseClusterGraph(vertices=np.array([0, 1]),
                           indptr=np.array([0, 1, 1], np.int64),
                           indices=np.array([1], np.int32))
    with pytest.raises(ValueError, match="out-degree"):
        network_matrix_sparse([g], 2)


# ---------------------------------------------------------------------------
# sample_sparse across families (and the satellite family fixes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
@pytest.mark.parametrize("self_loops", [True, False])
def test_sample_sparse_matches_sample(family, self_loops):
    """Dense snapshots derive from sparse ones (identical rng stream),
    for every family, with and without self-loops."""
    n, c = 30, 3
    spec = topology.make_spec(family, n=n, c=c, self_loops=self_loops)
    sparse = spec.build().sample_sparse(np.random.default_rng(3), 0)
    dense = spec.build().sample(np.random.default_rng(3), 0)
    for g_sp, g_dn in zip(sparse, dense):
        assert np.array_equal(g_sp.vertices, g_dn.vertices)
        assert np.array_equal(g_sp.dense().W, g_dn.W)


@pytest.mark.parametrize("family", sorted(ALL_FAMILIES))
def test_self_loops_false_is_honored(family):
    """No family silently reintroduces a self-loop when
    ``self_loops=False`` (satellite: the ``ensure_positive_out_degree``
    fallback used to).  Singleton clusters are the documented exception:
    a positive out-degree forces the self-loop there."""
    for n, c in [(2, 1), (3, 1), (12, 3), (30, 3)]:
        model = topology.make_spec(family, n=n, c=c,
                                   self_loops=False).build()
        rng = np.random.default_rng(11)
        for t in range(3):
            for g in model.sample_sparse(rng, t):
                W = g.dense().W
                assert (W.sum(axis=1) > 0).all(), (family, n, t)
                if g.size > 1:
                    assert np.trace(W) == 0, (family, n, t)


@pytest.mark.parametrize("s", [1, 2, 3])
@pytest.mark.parametrize("self_loops", [True, False])
def test_k_regular_tiny_clusters(s, self_loops):
    """Satellite regression: ``k_range`` far above the cluster size must
    clamp to a feasible degree -- with ``self_loops=False`` the max is
    ``s - 1`` (shift 0 is forbidden), which the old ``min(k, s)`` clamp
    exceeded, raising inside ``k_regular_digraph``."""
    model = topology.make_spec("k_regular", n=s, c=1,
                               k_range=(6, 7, 8, 9), p_fail=0.0,
                               self_loops=self_loops).build()
    rng = np.random.default_rng(0)
    (g,) = model.sample(rng, 0)
    W = g.W
    assert (W.sum(axis=1) > 0).all()
    if not self_loops and s > 1:
        assert np.trace(W) == 0
        assert (W.sum(axis=1) == s - 1).all()


def test_ensure_positive_out_degree_self_loop_policy():
    W = np.zeros((4, 4), np.int8)
    W[0, 1] = 1
    repaired = ensure_positive_out_degree(W, self_loops=False)
    assert (repaired.sum(axis=1) > 0).all()
    assert np.trace(repaired) == 0         # non-self repair edges
    # default path unchanged (bitwise-compatible with history)
    legacy = ensure_positive_out_degree(W)
    assert np.trace(legacy) == 3
    # singleton: the self-loop is the only possible edge
    one = ensure_positive_out_degree(np.zeros((1, 1), np.int8),
                                     self_loops=False)
    assert one[0, 0] == 1


@pytest.mark.parametrize("family", ["ring", "hub"])
def test_native_cluster_sparse_matches_cluster_w(family):
    """Ring and Hub emit CSR natively (no (s, s) scratch); pinned equal
    to the dense ``_cluster_W`` construction."""
    for self_loops in (True, False):
        for s in (1, 2, 3, 8):
            model = topology.make_spec(family, n=s, c=1,
                                       self_loops=self_loops).build()
            rng = np.random.default_rng(5)
            verts = np.arange(s)
            g_sp = model._cluster_sparse(rng, 0, verts)
            W = model._cluster_W(np.random.default_rng(5), 0, verts)
            assert np.array_equal(g_sp.dense().W, W), (family, self_loops,
                                                       s)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _kernel_inputs(n=13, p=37, seed=0):
    rng = np.random.default_rng(seed)
    A = _random_A(rng, n)
    idx, w = SparseA.from_dense(A).ell()
    X = rng.standard_normal((n, p)).astype(np.float32)
    tau = (rng.random(n) < 0.6).astype(np.float32)
    active = (rng.random(n) < 0.8).astype(np.float32)
    weights = rng.random(n).astype(np.float32)
    return (jnp.asarray(A), jnp.asarray(idx), jnp.asarray(w),
            jnp.asarray(X), jnp.asarray(tau), jnp.asarray(active),
            jnp.asarray(weights))


def test_sparse_mix_matches_dense():
    A, idx, w, X, tau, *_ = _kernel_inputs()
    m = jnp.float32(1.0)
    (dense,), _ = ops.mix_aggregate_grouped(A, tau, m, (X,), chunk=128)
    (sparse,), _ = ops.mix_aggregate_grouped((idx, w), tau, m, (X,),
                                             chunk=128)
    assert sparse.dtype == X.dtype
    assert np.allclose(dense, sparse, atol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
def test_sparse_mix_aggregate_matches_dense(masked):
    A, idx, w, X, tau, active, weights = _kernel_inputs(seed=masked)
    kw = (dict(active=active, weights=weights) if masked else {})
    m = jnp.float32(float(np.asarray(tau).sum()) or 1.0)
    (dm,), (da,) = ops.mix_aggregate_grouped(A, tau, m, (X,), chunk=128,
                                             **kw)
    (sm,), (sa,) = ops.mix_aggregate_grouped((idx, w), tau, m, (X,),
                                             chunk=128, **kw)
    assert np.allclose(dm, sm, atol=1e-5)
    assert np.allclose(da, sa, atol=1e-5)
    # aggregate-only path agrees with the fused row
    (sa2,) = ops.aggregate_grouped((idx, w), tau, m, (X,), chunk=128, **kw)
    assert np.allclose(da, sa2, atol=1e-5)


def test_combine_weights_ell_matches_dense():
    A, idx, w, X, tau, active, weights = _kernel_inputs(seed=3)
    m = jnp.float32(3.0)
    dense = ops.combine_weights(A, tau, m, active, weights)
    sparse = ops.combine_weights((idx, w), tau, m, active, weights)
    assert np.allclose(dense, sparse, atol=1e-6)


def test_combine_weights_m_zero_guard():
    """Satellite regression: an all-dropped round (m == 0) must yield
    the zero combine row, not inf/nan -- and the guard must be inert for
    m != 0 (identical to the unguarded divide)."""
    A, idx, w, X, tau, active, weights = _kernel_inputs(seed=4)
    for form in (A, (idx, w)):
        row = np.asarray(ops.combine_weights(form, tau, jnp.float32(0.0)))
        assert (row == 0.0).all() and np.isfinite(row).all()
    # inert for m != 0: exactly einsum / m
    got = np.asarray(ops.combine_weights(A, tau, jnp.float32(5.0)))
    ref = np.einsum("i,ij->j", np.asarray(tau, np.float32),
                    np.asarray(A, np.float32)) / np.float32(5.0)
    assert np.allclose(got, ref, atol=0, rtol=1e-6)


def test_m_zero_guard_through_round():
    """The guard holds end to end: aggregate with m = 0 returns the
    zero row, so the global update degenerates to identity."""
    A, idx, w, X, tau, active, weights = _kernel_inputs(seed=5)
    (agg,) = ops.aggregate_grouped((idx, w), tau, jnp.float32(0.0), (X,),
                                   chunk=128)
    assert (np.asarray(agg) == 0.0).all()
    (agg_d,) = ops.aggregate_grouped(A, tau, jnp.float32(0.0), (X,),
                                     chunk=128)
    assert (np.asarray(agg_d) == 0.0).all()
