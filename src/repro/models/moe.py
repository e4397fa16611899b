"""Mixture-of-Experts FFN: top-k router + grouped expert GEMMs.

Two compute paths with identical semantics:

* ``ragged`` -- sort tokens by expert and run grouped matmuls via
  ``jax.lax.ragged_dot`` (TPU-native grouped GEMM; FLOPs proportional to
  *active* experts, which keeps the roofline's MODEL_FLOPS/HLO_FLOPs ratio
  honest).  No token dropping: every token's top-k experts are honored.
* ``dense`` -- the oracle: evaluate every expert on every token and
  combine with the routing weights.  Exact but E/k times the FLOPs; used
  for correctness tests and tiny models.

Sharding (DESIGN §5): default is tensor-parallel experts -- expert weights
shard over the ``model`` axis on the ffn dim, routing stays local, and only
the usual MLP reduce crosses devices.  The expert-parallel all_to_all
variant is evaluated in the §Perf hillclimb.

Shared experts (DeepSeek-V2) are a plain always-on SwiGLU branch.
The auxiliary load-balance loss is the Switch/GShard form
``E * sum_e f_e * P_e``.

Gates: the top-k softmax probabilities, renormalized to sum to one
unless ``cfg.norm_topk_prob`` is off (DeepSeek-V2 keeps them raw), then
times ``cfg.routed_scaling``.

Expert share (expert parallelism's per-chip layer): with
``cfg.experts_held`` set the layer holds only the routed experts
``[expert_offset, expert_offset + experts_held)`` (their weights lead
with that many rows), routes every token over all ``n_experts`` at the
router's full width, and computes the part of the result its own experts
give: ``routed_scaling * sum_{e in top-k and held} g_e FFN_e(x)``, plus
the shared experts, which every share computes alike.  What the absent
experts would add is left out.  The ``ragged`` path sorts the held slots
first, by expert, into grouped matmuls over the held experts alone; the
other slots ride behind the groups and are zeroed.  No token is dropped.

Named scopes: ``moe_router`` (router, top-k, gates, aux loss),
``moe_experts`` (dispatch, grouped matmuls, combine) and ``moe_shared``.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import lecun_normal, mlp_apply, mlp_init

PyTree = Any

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, dtype) -> PyTree:
    d, ff, E = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": lecun_normal(ks[0], (d, cfg.n_experts), jnp.float32),
        "gate": lecun_normal(ks[1], (E, d, ff), dtype) / jnp.sqrt(1.0),
        "up": lecun_normal(ks[2], (E, d, ff), dtype),
        "down": lecun_normal(ks[3], (E, ff, d), dtype),
    }
    # lecun_normal normalizes by shape[0]=E; fix fan-in to d / ff.
    p["gate"] = p["gate"] * jnp.sqrt(E / d).astype(dtype)
    p["up"] = p["up"] * jnp.sqrt(E / d).astype(dtype)
    p["down"] = p["down"] * jnp.sqrt(E / ff).astype(dtype)
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.n_shared_experts * ff,
                               "swiglu", dtype)
    return p


def _route(cfg: ModelConfig, p: PyTree, xf: jnp.ndarray):
    """xf (T, d) -> weights (T, k), ids (T, k), aux_loss (scalar)."""
    E, k = cfg.n_experts, cfg.experts_per_token
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if cfg.routed_scaling != 1.0:
        w = w * cfg.routed_scaling
    # Switch-style load-balance: f_e = token fraction routed to e,
    # P_e = mean router probability of e.
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)      # (T, k, E)
    f = onehot.mean(axis=(0, 1)) * E                        # E * token fraction
    P = probs.mean(axis=0)
    aux = jnp.sum(f * P)                                    # = E * sum_e frac_e P_e
    return w.astype(xf.dtype), ids, aux


def _experts_ragged(p: PyTree, xs: jnp.ndarray, group_sizes: jnp.ndarray,
                    dtype, keep=None) -> jnp.ndarray:
    """Grouped SwiGLU over expert-sorted rows xs (Tk, d).  ``keep``
    (Tk, 1) marks the rows inside the groups when they do not cover xs:
    the grouped matmuls leave the others undefined, so they are zeroed
    after each (and xs before), which keeps them out of the gradients."""
    mask = (lambda a: a) if keep is None else (
        lambda a: jnp.where(keep, a, jnp.zeros((), a.dtype)))
    xs = mask(xs)
    g = mask(jax.lax.ragged_dot(xs, p["gate"], group_sizes))
    u = mask(jax.lax.ragged_dot(xs, p["up"], group_sizes))
    h = (jax.nn.silu(g) * u).astype(dtype)
    return mask(jax.lax.ragged_dot(h, p["down"], group_sizes))


def _dispatch_ragged(cfg: ModelConfig, p: PyTree, xf, w, ids) -> jnp.ndarray:
    """Expert dispatch + grouped GEMMs + combine for pre-routed tokens."""
    T, d = xf.shape
    k, E = cfg.experts_per_token, cfg.held_experts
    flat_ids = ids.reshape(-1)                        # (T*k,)
    held = None
    if cfg.experts_held:
        # the held experts' slots first, by local id; the rest after
        local = flat_ids - cfg.expert_offset
        held = (local >= 0) & (local < E)
        flat_ids = jnp.where(held, local, E)
    order = jnp.argsort(flat_ids)                     # stable
    token_of = order // k                             # source row per slot
    xs = jnp.take(xf, token_of, axis=0)               # (T*k, d)
    group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)
    keep = None if held is None else jnp.take(held, order)[:, None]
    ys = _experts_ragged(p, xs, group_sizes, xf.dtype, keep)
    wflat = jnp.take(w.reshape(-1), order)            # weight per sorted slot
    return jnp.zeros((T, d), xf.dtype).at[token_of].add(
        ys * wflat[:, None].astype(xf.dtype))


def _moe_ragged(cfg: ModelConfig, p: PyTree, xf: jnp.ndarray, w, ids
                ) -> jnp.ndarray:
    T, d = xf.shape
    C = cfg.moe_chunk
    if not (C and T > C and T % C == 0):
        return _dispatch_ragged(cfg, p, xf, w, ids)

    # token-chunked dispatch (§Perf): the (T*k, d)/(T*k, ff) dispatch
    # buffers never materialize for the full batch -- only per chunk.
    # Routing is global (identical weights/ids), so this is exact.
    nC = T // C
    xs = xf.reshape(nC, C, d)
    ws = w.reshape(nC, C, -1)
    idc = ids.reshape(nC, C, -1)

    def body(carry, xs_):
        xc, wc, ic = xs_
        return carry, _dispatch_ragged(cfg, p, xc, wc, ic)

    _, outs = jax.lax.scan(body, None, (xs, ws, idc))
    return outs.reshape(T, d)


def _moe_dense(cfg: ModelConfig, p: PyTree, xf: jnp.ndarray, w, ids
               ) -> jnp.ndarray:
    E = cfg.held_experts
    # absent experts' ids fall outside [0, E): their one-hot rows are 0
    onehot = jax.nn.one_hot(ids - cfg.expert_offset, E,
                            dtype=xf.dtype)           # (T, k, E)
    combine = jnp.einsum("tk,tke->te", w, onehot)     # (T, E)

    def expert(e):
        h = jax.nn.silu(xf @ p["gate"][e]) * (xf @ p["up"][e])
        return h @ p["down"][e]

    ys = jax.vmap(expert)(jnp.arange(E))              # (E, T, d)
    return jnp.einsum("te,etd->td", combine, ys)


def _expert_axis_size() -> int:
    """Size of the 'model' axis in the current abstract mesh (0 if none)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
            return int(mesh.shape["model"])
    except Exception:
        pass
    return 0


def _moe_expert_parallel(cfg: ModelConfig, p: PyTree, xf: jnp.ndarray, w,
                         ids) -> jnp.ndarray:
    """Expert-parallel MoE (beyond-paper §Perf): experts sharded over the
    'model' axis on the EXPERT dim, GShard-style capacity dispatch.

    vs the tensor-parallel layout ('tensor': ff dim sharded) this (a) runs
    full-width per-expert GEMMs (deepseek's ff/16 = 96 is MXU-misaligned),
    (b) combines with one psum of (T, d) instead of all-reducing the
    (T*k, d) partial rows, and (c) bounds dispatch memory by the per-expert
    capacity.  Tokens beyond capacity_factor=2 x fair share are dropped
    (standard GShard semantics; the ragged path remains the drop-free
    reference).
    """
    from jax.sharding import PartitionSpec as P

    T, d = xf.shape
    k, E = cfg.experts_per_token, cfg.n_experts
    m = _expert_axis_size()
    cap = max(int(2.0 * T * k / E), 8)

    flat_ids = ids.reshape(-1)                          # (T*k,)
    # rank of each slot within its expert (deterministic, token order)
    order = jnp.argsort(flat_ids)
    sizes = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(sizes) - sizes
    rank_sorted = jnp.arange(T * k) - jnp.take(starts, flat_ids[order])
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap
    wflat = w.reshape(-1)

    def body(gate, up, down, xf, flat_ids, rank, keep, wflat):
        my = jax.lax.axis_index("model")
        E_loc = gate.shape[0]
        e_loc = flat_ids - my * E_loc
        mine = keep & (e_loc >= 0) & (e_loc < E_loc)
        e_loc = jnp.clip(e_loc, 0, E_loc - 1)
        slot_tok = jnp.arange(T * k) // k
        rows = jnp.take(xf, slot_tok, axis=0)           # (T*k, d)
        buf = jnp.zeros((E_loc, cap, d), xf.dtype).at[
            (e_loc, jnp.clip(rank, 0, cap - 1))].add(
            rows * mine[:, None].astype(xf.dtype))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, gate)) \
            * jnp.einsum("ecd,edf->ecf", buf, up)
        y = jnp.einsum("ecf,efd->ecd", h.astype(xf.dtype), down)
        back = y[(e_loc, jnp.clip(rank, 0, cap - 1))]   # (T*k, d)
        contrib = back * (wflat * mine.astype(wflat.dtype))[:, None]
        out = jnp.zeros((T, d), xf.dtype).at[slot_tok].add(
            contrib.astype(xf.dtype))
        return jax.lax.psum(out, "model")

    out = jax.shard_map(
        body,
        in_specs=(P("model"), P("model"), P("model"),
                  P(None, None), P(None), P(None), P(None), P(None)),
        out_specs=P(None, None), check_vma=False,
    )(p["gate"], p["up"], p["down"], xf, flat_ids, rank, keep, wflat)
    return out


def moe_apply(cfg: ModelConfig, p: PyTree, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    with jax.named_scope("moe_router"):
        w, ids, aux = _route(cfg, p, xf)              # router on all tokens
    with jax.named_scope("moe_experts"):
        if (cfg.moe_sharding == "expert" and not cfg.experts_held
                and _expert_axis_size() > 1
                and cfg.n_experts % _expert_axis_size() == 0):
            out = _moe_expert_parallel(cfg, p, xf, w, ids)
        elif cfg.moe_impl == "ragged":
            out = _moe_ragged(cfg, p, xf, w, ids)
        else:
            out = _moe_dense(cfg, p, xf, w, ids)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            out = out + mlp_apply(p["shared"], xf, "swiglu")
    return out.reshape(B, S, d), aux
