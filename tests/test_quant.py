"""Quantized payload groups end to end (ISSUE 8 tentpole).

Covers: ``QuantSpec`` validation + dict round-trip, per-storage
quantize/dequantize error bounds, nibble pack/unpack exactness, the
error-feedback fp32 identity, stochastic-rounding determinism and
unbiasedness, fused-dequant kernel parity (dense + sparse, with
straggler masks), round-fn backends against the einsum-quant oracle,
scan == sequential with the quantizer state as carry, plan JSON v5
round-trips (and v4 payloads loading quant-free), the backend-support
matrix in ``resolve_backend``, engine-level execution from both config
sources, the compressed-bytes gate ratios, and int8+EF convergence
tracking fp32 where EF-off int4 measurably diverges.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (D2DNetwork, FederatedServer, ServerConfig,
                        client_deltas, make_round_fn, make_scanned_rounds)
from repro.core.rounds import MIXING_BACKENDS
from repro.core.sparse import SparseA
from repro.fl import ExecutionConfig, RoundPlan, make_engine, \
    resolve_backend
from repro.fl import packing
from repro.fl.packing import QuantSpec
from repro.kernels.mixing.ops import (aggregate_grouped,
                                      mix_aggregate_grouped)

jax.config.update("jax_enable_x64", False)

STORAGES = ("int8", "int4", "fp8")
# worst-case round-trip error per value, as a fraction of the block
# absmax: half a grid step for the integer grids, the e4m3 mantissa
# width (3 bits => rel err <= 2^-4, with headroom) for fp8
_ERR_FRAC = {"int8": 0.5 / 127, "int4": 0.5 / 7, "fp8": 0.08}


def _spec_for(storage, block=None):
    if block is None:
        block = 256 if storage == "int4" else 128
    return QuantSpec(storage=storage, block=block)


# ---------------------------------------------------------------------------
# QuantSpec validation + serialization
# ---------------------------------------------------------------------------

def test_quantspec_rejects_bad_config():
    with pytest.raises(ValueError, match="storage"):
        QuantSpec(storage="int2")
    with pytest.raises(ValueError, match="rounding"):
        QuantSpec(rounding="banker")
    with pytest.raises(ValueError, match="stochastic"):
        QuantSpec(storage="fp8", rounding="stochastic")
    with pytest.raises(ValueError, match="block"):
        QuantSpec(storage="int8", block=64)
    with pytest.raises(ValueError, match="block"):
        QuantSpec(storage="int4", block=384)  # not a multiple of 256


def test_quantspec_dict_roundtrip():
    spec = QuantSpec(storage="int4", block=512, rounding="stochastic",
                     error_feedback=False, seed=7)
    back = QuantSpec.from_dict(json.loads(json.dumps(spec.as_dict())))
    assert back == spec


# ---------------------------------------------------------------------------
# quantize/dequantize: error bounds, zero blocks, nibbles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage", STORAGES)
def test_roundtrip_error_bounded_per_block(storage):
    quant = _spec_for(storage)
    rng = np.random.default_rng(0)
    buf = jnp.asarray(rng.standard_normal((4, 2 * quant.block)) * 3.0,
                      jnp.float32)
    stored, scales = packing.quantize_group(buf, quant)
    dq = packing.dequantize_group(stored, scales, quant)
    err = np.abs(np.asarray(dq) - np.asarray(buf)).reshape(
        4, -1, quant.block)
    absmax = np.abs(np.asarray(buf)).reshape(4, -1, quant.block) \
        .max(axis=2, keepdims=True)
    assert (err <= _ERR_FRAC[storage] * absmax + 1e-7).all()


@pytest.mark.parametrize("storage", STORAGES)
def test_zero_block_dequantizes_to_exact_zeros(storage):
    quant = _spec_for(storage)
    buf = jnp.zeros((2, quant.block), jnp.float32)
    stored, scales = packing.quantize_group(buf, quant)
    assert (np.asarray(scales) == 0).all()
    assert (np.asarray(packing.dequantize_group(stored, scales, quant))
            == 0).all()


def test_nibble_pack_unpack_exact():
    rng = np.random.default_rng(1)
    v = jnp.asarray(rng.integers(-8, 8, size=(3, 512)), jnp.int8)
    packed = packing._pack_nibbles(v, 256)
    assert packed.shape == (3, 256)
    np.testing.assert_array_equal(
        np.asarray(packing._unpack_nibbles(packed, 256)), np.asarray(v))
    # block halves: byte j of a block holds values j (low) and j + 128
    b = np.asarray(packed).astype(np.uint8)
    np.testing.assert_array_equal((b[:, :128] & 0x0F).astype(np.int8),
                                  np.asarray(v)[:, :128] & 0x0F)
    np.testing.assert_array_equal((b[:, :128] >> 4).astype(np.int8),
                                  np.asarray(v)[:, 128:256] & 0x0F)


@pytest.mark.parametrize("storage", STORAGES)
def test_kernel_dequant_tile_matches_host_bitwise(storage):
    """The kernels' in-VMEM dequant is the host dequant, bit for bit."""
    from repro.kernels.mixing.fused import dequant_tile

    quant = _spec_for(storage)
    rng = np.random.default_rng(4)
    buf = jnp.asarray(rng.standard_normal((5, 4 * quant.block)),
                      jnp.float32)
    stored, scales = packing.quantize_group(buf, quant)
    np.testing.assert_array_equal(
        np.asarray(dequant_tile(stored, scales, storage=storage,
                                block=quant.block)),
        np.asarray(packing.dequantize_group(stored, scales, quant)))


def test_int4_grid_values_roundtrip_exact():
    """Values already on the int4 grid survive the round-trip bitwise."""
    quant = _spec_for("int4")
    rng = np.random.default_rng(2)
    scale = 0.25
    grid = rng.integers(-7, 8, size=(2, quant.block)) * scale
    buf = jnp.asarray(grid, jnp.float32)
    stored, scales = packing.quantize_group(buf, quant)
    dq = packing.dequantize_group(stored, scales, quant)
    np.testing.assert_allclose(np.asarray(dq), grid, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# error feedback + stochastic rounding
# ---------------------------------------------------------------------------

def _tree(rng, n, bf16_cols=384, fp32_cols=130):
    return {"w": jnp.asarray(rng.standard_normal((n, bf16_cols)),
                             jnp.bfloat16),
            "b": jnp.asarray(rng.standard_normal((n, fp32_cols)),
                             jnp.float32)}


def test_error_feedback_residual_is_exact_roundtrip_error():
    rng = np.random.default_rng(3)
    n = 4
    tree = _tree(rng, n)
    quant = _spec_for("int8")
    spec = packing.pack_spec(tree, quant=quant)
    bufs = packing.pack(tree, spec)
    residuals, _ = packing.init_quant_state(spec, n)
    # seed non-zero residuals: one EF step first
    _, _, residuals = packing.quantize_packed(bufs, spec, residuals)
    stored, scales, new_res = packing.quantize_packed(bufs, spec, residuals)
    dq = packing.dequantize_packed(stored, scales, spec)
    for b, r, s, d in zip(bufs, residuals, new_res, dq):
        want = (np.asarray(b, np.float32) + np.asarray(r)) - np.asarray(d)
        np.testing.assert_array_equal(np.asarray(s), want)


def test_stochastic_rounding_deterministic_given_key():
    rng = np.random.default_rng(4)
    quant = QuantSpec(storage="int8", block=128, rounding="stochastic")
    buf = jnp.asarray(rng.standard_normal((3, 256)), jnp.float32)
    key = jax.random.PRNGKey(0)
    s1, sc1 = packing.quantize_group(buf, quant, key)
    s2, sc2 = packing.quantize_group(buf, quant, key)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(sc1), np.asarray(sc2))
    with pytest.raises(ValueError, match="PRNG key"):
        packing.quantize_group(buf, quant, None)


def test_stochastic_rounding_unbiased():
    quant = QuantSpec(storage="int8", block=128, rounding="stochastic")
    buf = jnp.full((1, 128), 0.35, jnp.float32)
    # fix the absmax so the grid is known: one value at 1.0
    buf = buf.at[0, 0].set(1.0)
    acc = np.zeros(128)
    trials = 400
    for i in range(trials):
        s, sc = packing.quantize_group(buf, quant, jax.random.PRNGKey(i))
        acc += np.asarray(packing.dequantize_group(s, sc, quant))[0]
    np.testing.assert_allclose(acc / trials, np.asarray(buf)[0],
                               atol=2e-3)


# ---------------------------------------------------------------------------
# kernel parity: fused dequant epilogue vs dequantized einsum oracle
# ---------------------------------------------------------------------------

def _quant_inputs(storage, n=8, seed=5):
    """Quantized wire payload + mask, with the rounds-layer straggler
    recipe already applied: dropped clients are zeroed out of the mixed
    leg by zeroing their rows of the *scales* (one multiply on the tiny
    side buffer, never the payload); the aggregate leg re-masks through
    the combine row, which is idempotent for 0/1 masks."""
    rng = np.random.default_rng(seed)
    tree = _tree(rng, n)
    quant = _spec_for(storage)
    spec = packing.pack_spec(tree, quant=quant)
    bufs = packing.pack(tree, spec)
    stored, scales, _ = packing.quantize_packed(bufs, spec)
    active = rng.integers(0, 2, n).astype(np.float32)
    scales = tuple(s * jnp.asarray(active)[:, None] for s in scales)
    dq = packing.dequantize_packed(stored, scales, spec)
    A = rng.random((n, n)).astype(np.float32)
    A = A / np.clip(A.sum(axis=0, keepdims=True), 1e-6, None)
    tau = rng.integers(0, 2, n).astype(np.float32)
    m = np.float32(max(1.0, (tau * active).sum()))
    return quant, spec, stored, scales, dq, jnp.asarray(A), \
        jnp.asarray(tau), jnp.asarray(active), jnp.float32(m)


def _oracle(A, tau, m, dq, active):
    """Mix the (already row-masked) dequantized buffers, aggregate with
    ``tau * active`` -- the einsum-quant recipe."""
    outs_mixed, outs_agg = [], []
    act = np.asarray(active)
    for d in dq:
        mixed = np.asarray(A) @ np.asarray(d, np.float32)
        outs_mixed.append(mixed)
        outs_agg.append(np.einsum(
            "i,ip->p", np.asarray(tau) * act, mixed) / float(m))
    return outs_mixed, outs_agg


@pytest.mark.parametrize("storage", STORAGES)
def test_dense_kernels_match_oracle(storage):
    quant, spec, stored, scales, dq, A, tau, active, m = \
        _quant_inputs(storage)
    ref_mixed, ref_agg = _oracle(A, tau, m, dq, active)
    got_mixed, got_agg = mix_aggregate_grouped(
        A, tau, m, stored, scales=scales, quant=quant, chunk=512,
        active=active)
    for gm, ga, rm, ra in zip(got_mixed, got_agg, ref_mixed, ref_agg):
        np.testing.assert_allclose(np.asarray(gm), rm, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(ga), ra, rtol=2e-5,
                                   atol=2e-5)
    agg_only = aggregate_grouped(A, tau, m, stored, scales=scales,
                                 quant=quant, chunk=512, active=active)
    for ga, ra in zip(agg_only, ref_agg):
        np.testing.assert_allclose(np.asarray(ga), ra, rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("storage", STORAGES)
def test_sparse_kernels_match_oracle(storage):
    quant, spec, stored, scales, dq, A, tau, active, m = \
        _quant_inputs(storage, seed=6)
    # sparsify: zero out most entries, keep ELL form of the survivors
    rng = np.random.default_rng(7)
    mask = rng.random(A.shape) < 0.4
    A = jnp.asarray(np.asarray(A) * mask, jnp.float32)
    idx_np, w_np = SparseA.from_dense(np.asarray(A)).ell()
    idx, w = jnp.asarray(idx_np), jnp.asarray(w_np)
    ref_mixed, ref_agg = _oracle(A, tau, m, dq, active)
    got_mixed, got_agg = mix_aggregate_grouped(
        (idx, w), tau, m, stored, scales=scales, quant=quant, chunk=512,
        active=active)
    for gm, ga, rm, ra in zip(got_mixed, got_agg, ref_mixed, ref_agg):
        np.testing.assert_allclose(np.asarray(gm), rm, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(ga), ra, rtol=2e-5,
                                   atol=2e-5)
    agg_only = aggregate_grouped(
        (idx, w), tau, m, stored, scales=scales, quant=quant, chunk=512,
        active=active)
    for ga, ra in zip(agg_only, ref_agg):
        np.testing.assert_allclose(np.asarray(ga), ra, rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# round functions: backends vs einsum-quant oracle, scan == sequential
# ---------------------------------------------------------------------------

def quad_loss(params, batch):
    x = params["x"]
    b, = batch
    return 0.5 * jnp.sum((x - b.mean(axis=0)) ** 2)


def _round_setup(seed=9, n=6, p=130, T=3, B=2):
    rng = np.random.default_rng(seed)
    batches = (jnp.asarray(rng.standard_normal((n, T, B, p)), jnp.float32),)
    A = jnp.asarray(rng.random((n, n)), jnp.float32)
    A = A / jnp.clip(A.sum(axis=0, keepdims=True), 1e-6)
    tau = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
    m = jnp.float32(max(1.0, float(tau.sum())))
    return batches, A, tau, m, jnp.float32(0.1), {"x": jnp.zeros(p)}


def _qstate_for(params, n, quant):
    spec = packing.pack_spec(
        jax.tree.map(lambda p: jax.ShapeDtypeStruct((n,) + p.shape,
                                                    p.dtype), params),
        quant=quant)
    return packing.init_quant_state(spec, n)


def test_quant_backends_agree_and_share_qstate():
    batches, A, tau, m, eta, params = _round_setup()
    n = int(A.shape[0])
    quant = QuantSpec(storage="int8", block=128)
    qstate0 = _qstate_for(params, n, quant)
    idx_np, w_np = SparseA.from_dense(np.asarray(A)).ell()
    sparse_A = (jnp.asarray(idx_np), jnp.asarray(w_np))
    results = {}
    for backend in MIXING_BACKENDS:
        fn = make_round_fn(quad_loss, mixing_backend=backend, chunk=512,
                           quant=quant)
        Aarg = sparse_A if backend.startswith("sparse") else A
        new, _, qs = fn(params, batches, Aarg, tau, m, eta, None, qstate0)
        results[backend] = (np.asarray(new["x"]), qs)
    ref, ref_qs = results["einsum"]
    for backend, (got, qs) in results.items():
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                   err_msg=backend)
        # the quantizer runs before the mixing backend: state is bitwise
        # identical across all of them
        for a, b in zip(qs[0], ref_qs[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quant_round_fn_requires_qstate_and_valid_backend():
    with pytest.raises(ValueError, match="mixing_backend must be one of"):
        make_round_fn(quad_loss, mixing_backend="nope", quant=QuantSpec())
    with pytest.raises(ValueError, match="multiple of quant.block"):
        make_round_fn(quad_loss, mixing_backend="fused", chunk=512,
                      quant=QuantSpec(block=768))
    fn = make_round_fn(quad_loss, mixing_backend="einsum",
                       quant=QuantSpec())
    batches, A, tau, m, eta, params = _round_setup()
    with pytest.raises(ValueError, match="quantizer state"):
        fn(params, batches, A, tau, m, eta)


def test_quant_scan_matches_sequential_with_ef_carry():
    K, n, p = 4, 6, 130
    rng = np.random.default_rng(10)
    batches_seq = (jnp.asarray(
        rng.standard_normal((K, n, 3, 2, p)), jnp.float32),)
    A_seq = jnp.asarray(rng.random((K, n, n)), jnp.float32)
    A_seq = A_seq / jnp.clip(A_seq.sum(axis=1, keepdims=True), 1e-6)
    tau_seq = jnp.asarray(rng.integers(0, 2, (K, n)), jnp.float32)
    m_seq = jnp.maximum(tau_seq.sum(axis=1), 1.0)
    eta_seq = jnp.full((K,), 0.1, jnp.float32)
    params = {"x": jnp.zeros(p)}
    quant = QuantSpec(storage="int4", block=256)
    qstate0 = _qstate_for(params, n, quant)

    fn = make_round_fn(quad_loss, mixing_backend="aggregate", chunk=512,
                       quant=quant)
    seq_params, qs = params, qstate0
    for t in range(K):
        seq_params, _, qs = fn(seq_params, (batches_seq[0][t],),
                               A_seq[t], tau_seq[t], m_seq[t], eta_seq[t],
                               None, qs)
    scanned = make_scanned_rounds(quad_loss, K,
                                  mixing_backend="aggregate", chunk=512,
                                  quant=quant)
    final, params_seq, final_qs = scanned(
        params, batches_seq, A_seq, tau_seq, m_seq, eta_seq, None, qstate0)
    np.testing.assert_array_equal(np.asarray(final["x"]),
                                  np.asarray(seq_params["x"]))
    np.testing.assert_array_equal(np.asarray(params_seq["x"][-1]),
                                  np.asarray(final["x"]))
    for a, b in zip(final_qs[0], qs[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# plan serialization (JSON v5) + backend-support matrix
# ---------------------------------------------------------------------------

def _plan(t_max=3, seed=3, n=12):
    net = D2DNetwork(n=n, c=2, k_range=(4, 6), p_fail=0.1)
    cfg = ServerConfig(T=3, t_max=t_max, phi_max=0.3, seed=seed,
                       eta=lambda t: 0.2 / (1 + 0.3 * t))
    return RoundPlan.connectivity_aware(net, cfg)


def test_plan_quant_json_roundtrip_and_v4_loads():
    plan = _plan().with_quant(QuantSpec(storage="int4", block=512,
                                        error_feedback=False))
    back = RoundPlan.from_json(plan.to_json())
    assert back.quant == plan.quant
    assert back.allclose(plan)
    assert not back.allclose(plan.with_quant(None))

    # a v4 (pre-quant) payload still loads, as unquantized
    d = json.loads(plan.with_quant(None).to_json())
    assert d["version"] == 5
    d["version"] = 4
    d.pop("quant", None)
    v4 = RoundPlan.from_json(json.dumps(d))
    assert v4.quant is None


def test_plan_with_quant_validates_type():
    with pytest.raises(ValueError, match="QuantSpec"):
        _plan().with_quant({"storage": "int8"})


def test_resolve_backend_quant_matrix():
    q = QuantSpec()
    # every local backend quantizes (incl. the fused->aggregate upgrade)
    for backend in ("einsum", "fused", "aggregate", "sparse",
                    "sparse_aggregate"):
        resolve_backend(ExecutionConfig(backend=backend, quant=q))
    # so does the materializing leg record_mixed keeps alive
    assert resolve_backend(ExecutionConfig(
        backend="fused", record_mixed=True, quant=q)) == "fused"
    # stream runtime: no well-defined EF residual for stale cohorts
    from repro.fl import StreamConfig
    with pytest.raises(ValueError, match="stream"):
        resolve_backend(ExecutionConfig(backend="aggregate",
                                        stream=StreamConfig(), quant=q))


def test_stream_engine_rejects_plan_quant():
    from repro.fl import StreamConfig
    plan = _plan().with_quant(QuantSpec())
    cfg = ExecutionConfig(backend="aggregate", stream=StreamConfig())
    engine = make_engine(cfg, quad_loss)
    rng = np.random.default_rng(0)
    batches = [(jnp.asarray(rng.standard_normal((12, 3, 2, 4)),
                            jnp.float32),)] * plan.n_rounds
    with pytest.raises(ValueError, match="with_quant"):
        engine.execute(plan, {"x": jnp.zeros(4)}, batches)


# ---------------------------------------------------------------------------
# engine-level execution: cfg.quant and plan.quant
# ---------------------------------------------------------------------------

def _engine_run(cfg, plan=None, p=130):
    plan = plan if plan is not None else _plan()
    n = plan.n_clients
    rng = np.random.default_rng(8)
    targets = rng.standard_normal((n, p)).astype(np.float32)
    batches = [(jnp.asarray(
        targets[:, None, None, :]
        + 0.05 * rng.standard_normal((n, 3, 2, p)), jnp.float32),)
        for _ in range(plan.n_rounds)]
    engine = make_engine(cfg, quad_loss)
    params, hist = engine.execute(plan, {"x": jnp.zeros(p)}, batches)
    return np.asarray(params["x"]), hist


def test_engine_quant_sources_and_backends_agree():
    q = QuantSpec(storage="int8", block=128)
    plan = _plan()
    via_cfg, _ = _engine_run(
        ExecutionConfig(backend="aggregate", quant=q), plan)
    via_plan, _ = _engine_run(
        ExecutionConfig(backend="aggregate"), plan.with_quant(q))
    np.testing.assert_array_equal(via_cfg, via_plan)

    scanned, _ = _engine_run(
        ExecutionConfig(backend="aggregate", scan=True, quant=q), plan)
    np.testing.assert_allclose(scanned, via_cfg, rtol=1e-6, atol=1e-6)

    fused, _ = _engine_run(ExecutionConfig(backend="fused", quant=q), plan)
    np.testing.assert_allclose(fused, via_cfg, rtol=2e-5, atol=2e-5)

    fp32, _ = _engine_run(ExecutionConfig(backend="aggregate"), plan)
    assert np.abs(fp32 - via_cfg).max() > 0  # quant actually engaged


# ---------------------------------------------------------------------------
# compressed bytes: the CI gate ratios
# ---------------------------------------------------------------------------

def test_compressed_bytes_ratio_gate():
    """int4 on a bf16-majority tree and int8 on an fp32 tree both land
    at <= 0.3x the grouped full-precision wire bytes (scales included)
    -- the ratio the CI quant job asserts on the benchmark rows."""
    rng = np.random.default_rng(11)
    n = 4
    bf16_tree = {"w": jnp.asarray(rng.standard_normal((n, 4096)),
                                  jnp.bfloat16),
                 "b": jnp.asarray(rng.standard_normal((n, 256)),
                                  jnp.float32)}
    fp32_tree = {"w": jnp.asarray(rng.standard_normal((n, 4096)),
                                  jnp.float32)}
    for tree, storage in ((bf16_tree, "int4"), (fp32_tree, "int8")):
        spec = packing.pack_spec(tree)
        qspec = packing.pack_spec(tree, quant=_spec_for(storage, 512))
        ratio = qspec.quantized_nbytes(n) / spec.nbytes(n)
        assert ratio <= 0.3, (storage, ratio)
    # int8 on bf16 is only ~0.5x: the gate needs int4 there
    qspec = packing.pack_spec(bf16_tree, quant=_spec_for("int8"))
    assert packing.pack_spec(bf16_tree).nbytes(n) * 0.3 \
        < qspec.quantized_nbytes(n)


# ---------------------------------------------------------------------------
# convergence: int8+EF tracks fp32; EF-off int4 measurably diverges
# ---------------------------------------------------------------------------

def test_int8_ef_tracks_fp32_and_ef_off_int4_diverges():
    """The error-feedback claim on the quickstart workload shape: with EF
    on, int8 training lands within tolerance of the fp32 trajectory;
    dropping EF at the aggressive int4 setting loses measurably more."""
    K, n, p = 8, 6, 130
    rng = np.random.default_rng(12)
    targets = rng.standard_normal((n, p)).astype(np.float32)
    batches_seq = (jnp.asarray(
        targets[None, :, None, None, :]
        + 0.05 * rng.standard_normal((K, n, 3, 2, p)), jnp.float32),)
    A_seq = jnp.asarray(rng.random((K, n, n)), jnp.float32)
    A_seq = A_seq / jnp.clip(A_seq.sum(axis=1, keepdims=True), 1e-6)
    tau_seq = jnp.ones((K, n), jnp.float32)
    m_seq = jnp.full((K,), float(n), jnp.float32)
    eta_seq = jnp.full((K,), 0.15, jnp.float32)
    params = {"x": jnp.zeros(p)}

    def loss_of(x):
        return float(0.5 * np.mean(
            np.sum((x[None, :] - targets) ** 2, axis=1)))

    scanned = make_scanned_rounds(quad_loss, K, mixing_backend="einsum")
    fp32, _ = scanned(params, batches_seq, A_seq, tau_seq, m_seq, eta_seq)
    l_fp32 = loss_of(np.asarray(fp32["x"]))

    def run_q(quant):
        sc = make_scanned_rounds(quad_loss, K, mixing_backend="einsum",
                                 quant=quant)
        final, _, _ = sc(params, batches_seq, A_seq, tau_seq, m_seq,
                         eta_seq, None, _qstate_for(params, n, quant))
        return loss_of(np.asarray(final["x"]))

    l_int8_ef = run_q(QuantSpec(storage="int8", block=128,
                                error_feedback=True))
    l_int4_noef = run_q(QuantSpec(storage="int4", block=256,
                                  error_feedback=False))

    gap_ef = abs(l_int8_ef - l_fp32)
    gap_noef = abs(l_int4_noef - l_fp32)
    assert gap_ef <= 0.02 * max(l_fp32, 1e-6), (l_fp32, l_int8_ef)
    assert gap_noef > 5 * gap_ef, (l_fp32, l_int8_ef, l_int4_noef)
