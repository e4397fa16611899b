"""One-dispatch mesh runtime: scanned multi-round equivalence.

Property under test (ISSUE 2 tentpole): for a K-round time-varying
topology trajectory, K scanned mesh rounds (``make_scanned_train_steps``)
== K sequential ``train_step`` dispatches (bitwise) == the single-host
``make_scanned_rounds`` oracle (allclose), across mixing schedules
including the worker-sharded reduce-scatter 'fused_rs' path.

Two tiers:

* unmarked -- run on the real 1-CPU backend with a (1, 1) debug mesh:
  exercise the scan lifting, the fused_rs shard_map wiring, and the
  server's mesh+scan routing without forcing host devices (tier-1).
* ``mesh``-marked -- the full schedule x scan matrix on a forced 8-device
  CPU mesh in a subprocess (XLA device-count forcing must precede jax
  init).  Excluded from tier-1 by pytest.ini; run with ``-m mesh``.
"""

import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELPERS = os.path.join(REPO, "tests", "helpers")

XLA_8 = "--xla_force_host_platform_device_count=8"


def _run(args, env_extra=None, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


@functools.lru_cache(maxsize=1)
def _forced_host_devices_available() -> bool:
    """Gate the multi-process mesh tests on XLA_FLAGS host-device forcing
    actually yielding 8 devices on this install (it can be a no-op on
    exotic backends / pinned platform plugins).  Called from the test body
    (not a skipif marker) so tier-1 never pays the probe subprocess for a
    deselected mesh test."""
    r = _run(["-c", "import jax; print(len(jax.devices()))"],
             env_extra={"XLA_FLAGS": XLA_8}, timeout=120)
    return r.returncode == 0 and r.stdout.strip() == "8"


# ---------------------------------------------------------------------------
# full matrix on a forced 8-device mesh (subprocess; mesh tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.mesh
def test_mesh_scan_matrix_matches_sequential_and_oracle():
    if not _forced_host_devices_available():
        pytest.skip("XLA_FLAGS host-device forcing unavailable")
    r = _run([os.path.join(HELPERS, "mesh_scan_equivalence.py")],
             env_extra={"XLA_FLAGS": XLA_8})
    assert r.returncode == 0, r.stdout + r.stderr
    for mixing in ("einsum", "fused", "fused_rs", "ring"):
        assert f"OK scan mixing={mixing}" in r.stdout
        assert f"OK active mixing={mixing}" in r.stdout
    for mixing in ("einsum", "fused"):
        assert f"OK server scan mixing={mixing}" in r.stdout


# ---------------------------------------------------------------------------
# tier-1: scan lifting + fused_rs wiring on the real 1-CPU backend
# ---------------------------------------------------------------------------

def _tiny_setup(K=2):
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import Model

    mesh = make_debug_mesh((1, 1), axes=("data", "model"))
    cfg = get_config("stablelm-1.6b", reduced=True)
    cfg = cfg.__class__(**{**cfg.__dict__, "vocab_size": 64,
                           "name": "tiny-1dev"})
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    n, T, B, S = 1, 2, 2, 8
    toks = jnp.asarray(rng.integers(0, 64, size=(K, n, T, B, S + 1)),
                       jnp.int32)
    A_seq = jnp.ones((K, 1, 1), jnp.float32)
    tau_seq = jnp.asarray([[1.0]] * (K - 1) + [[0.0]], jnp.float32)
    m_seq = jnp.ones((K,), jnp.float32)
    eta_seq = jnp.asarray([0.05 / (1 + t) for t in range(K)], jnp.float32)
    return mesh, cfg, model, params, (toks, A_seq, tau_seq, m_seq, eta_seq)


# 'einsum' is exercised by the oracle test below and by the full
# 8-device matrix (-m mesh); keeping the 1-device parametrize to the two
# packed paths holds tier-1 under the 5-minute budget.
@pytest.mark.parametrize("mixing", ["fused", "fused_rs"])
def test_scanned_train_steps_bitwise_vs_sequential_1dev(mixing):
    from repro.fl import make_scanned_train_steps, make_train_step

    K = 2
    mesh, cfg, model, params, xs = _tiny_setup(K)
    toks, A_seq, tau_seq, m_seq, eta_seq = xs

    step = make_train_step(cfg, mesh, mixing=mixing)
    seq = params
    per_round = []
    for t in range(K):
        seq = step(seq, toks[t], A_seq[t], tau_seq[t], m_seq[t], eta_seq[t])
        per_round.append(seq)

    scanned = make_scanned_train_steps(cfg, mesh, K, mixing=mixing)
    final, params_seq = scanned(params, toks, A_seq, tau_seq, m_seq,
                                eta_seq)
    for a, b in zip(jax.tree.leaves(seq), jax.tree.leaves(final)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for t in range(K):
        got_t = jax.tree.map(lambda x: x[t], params_seq)
        for a, b in zip(jax.tree.leaves(per_round[t]),
                        jax.tree.leaves(got_t)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scanned_train_steps_match_single_host_oracle_1dev():
    """n=1 degenerates eq. 3+4 to x + (tau/m) * delta -- the mesh scan must
    still agree with the Algorithm-1 oracle trajectory."""
    from repro.core import rounds as ref_rounds
    from repro.fl import make_scanned_train_steps

    K = 2
    mesh, cfg, model, params, xs = _tiny_setup(K)
    toks, A_seq, tau_seq, m_seq, eta_seq = xs

    oracle = ref_rounds.make_scanned_rounds(model.loss, K)
    ref_final, _ = oracle(params, (toks[..., :-1], toks[..., 1:]), A_seq,
                          tau_seq, m_seq, eta_seq)
    scanned = make_scanned_train_steps(cfg, mesh, K, mixing="fused")
    final, _ = scanned(params, toks, A_seq, tau_seq, m_seq, eta_seq)
    for a, b in zip(jax.tree.leaves(ref_final), jax.tree.leaves(final)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   rtol=2e-4, atol=2e-5)


def test_tau_zero_round_is_identity_on_globals():
    """A round in which no client is sampled must leave the global params
    exactly unchanged (tau=0 => aggregate row is 0) on the mesh runtime."""
    from repro.fl import make_train_step

    mesh, cfg, model, params, xs = _tiny_setup(1)
    toks, A_seq, _, m_seq, eta_seq = xs
    step = make_train_step(cfg, mesh, mixing="fused_rs")
    out = step(params, toks[0], A_seq[0], jnp.zeros((1,), jnp.float32),
               m_seq[0], eta_seq[0])
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# tier-1: FederatedServer mesh + scan routing (1-device mesh, fedavg n=1)
# ---------------------------------------------------------------------------

def _mesh_server(scan_rounds, mesh, cfg, params):
    from repro.core import D2DNetwork, FederatedServer, ServerConfig

    n, T, B, S = 1, 2, 2, 8

    def sampler(r, t):
        return jnp.asarray(r.integers(0, 64, size=(n, T, B, S + 1)),
                           jnp.int32)

    net = D2DNetwork(n=1, c=1, k_range=(1, 1))
    scfg = ServerConfig(T=T, t_max=3, m_fixed=1, seed=5,
                        eta=lambda t: 0.05 / (1 + t))
    return FederatedServer(net, None, params, sampler, scfg,
                           algorithm="fedavg", mixing_backend="fused",
                           scan_rounds=scan_rounds, mesh=mesh,
                           model_cfg=cfg)


def test_server_mesh_scan_history_matches_sequential():
    mesh, cfg, model, params, _ = _tiny_setup(1)

    def l2(prm):
        return {"l2": float(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                for x in jax.tree.leaves(prm)))}

    s_seq = _mesh_server(False, mesh, cfg, params)
    h_seq = s_seq.run(eval_fn=l2)
    s_scan = _mesh_server(True, mesh, cfg, params)
    h_scan = s_scan.run(eval_fn=l2)

    assert len(h_seq.records) == len(h_scan.records) == 3
    for a, b in zip(h_seq.records, h_scan.records):
        assert (a.t, a.m, a.m_actual, a.d2s, a.d2d, a.eta) == \
            (b.t, b.m, b.m_actual, b.d2s, b.d2d, b.eta)
        assert a.metrics["l2"] == b.metrics["l2"]
    for x, y in zip(jax.tree.leaves(s_seq.params),
                    jax.tree.leaves(s_scan.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_server_mesh_requires_model_cfg_and_valid_mixing():
    from repro.core import D2DNetwork, FederatedServer, ServerConfig
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh((1, 1), axes=("data", "model"))
    net = D2DNetwork(n=1, c=1, k_range=(1, 1))
    scfg = ServerConfig(m_fixed=1)
    with pytest.raises(ValueError, match="model_cfg"):
        FederatedServer(net, None, {}, lambda r, t: None, scfg,
                        algorithm="fedavg", mesh=mesh)
    mesh_cfg = object()
    with pytest.raises(ValueError, match="mesh mixing"):
        FederatedServer(net, None, {}, lambda r, t: None, scfg,
                        algorithm="fedavg", mesh=mesh,
                        model_cfg=mesh_cfg, mixing_backend="aggregate")
