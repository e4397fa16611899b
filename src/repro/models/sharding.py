"""Parameter and activation sharding rules for the production mesh.

Rules map parameter tree paths to PartitionSpecs over the ``model`` axis
(tensor parallelism); the client/batch axes are handled by the callers
(``repro.fl.distributed`` for training, ``repro.launch.serve_lib`` for
inference).  Scanned layer stacks get a leading ``None`` (the layer axis is
never sharded).

Activation policy: the residual stream can be sequence-sharded over
``model`` between blocks (Megatron-style sequence parallelism) -- enabled
via ``set_activation_sharding``; XLA inserts the all-gather/reduce-scatter
pairs around attention/MLP.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

__all__ = ["param_specs", "set_activation_sharding", "constrain_seq",
           "cache_specs", "set_moe_sharding"]

# path-regex -> spec for the *parameter's own dims* (layer-stack axis added
# automatically when the leaf has one more dim than the rule expects).
_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings / head
    (r"embed$",                      P("model", None)),
    (r"lm_head$",                    P(None, "model")),
    (r"frontend_proj$",              P(None, None)),
    (r"final_norm(/scale|/bias)?$",  P(None)),
    # attention (GQA)
    (r"attn/(q|k|v)/w$",             P(None, "model")),
    (r"attn/(q|k|v)/b$",             P("model")),
    (r"attn/o/w$",                   P("model", None)),
    (r"attn/(q_norm|k_norm)$",       P(None)),
    # MLA
    (r"mla/wq_a$",                   P(None, None)),
    (r"mla/wq_b$",                   P(None, "model")),
    (r"mla/wq$",                     P(None, "model")),
    (r"mla/wkv_a$",                  P(None, None)),
    (r"mla/wkv_b$",                  P(None, "model")),
    (r"mla/wo$",                     P("model", None)),
    (r"mla/(q_norm|kv_norm)$",       P(None)),
    # dense MLP
    (r"mlp/(gate|up)$",              P(None, "model")),
    (r"mlp/down$",                   P("model", None)),
    # MoE (tensor-parallel experts: ffn dim sharded; see also the
    # expert-parallel override below)
    (r"moe/router$",                 P(None, None)),
    (r"moe/(gate|up)$",              P(None, None, "model")),
    (r"moe/down$",                   P(None, "model", None)),
    (r"moe/shared/(gate|up)$",       P(None, "model")),
    (r"moe/shared/down$",            P("model", None)),
    # SSM (mamba2)
    (r"ssm/(w_x|w_z|w_B|w_C|w_dt)$", P(None, "model")),
    (r"ssm/(dt_bias|A_log|D)$",      P("model")),
    (r"ssm/conv_(w|b)$",             P()),            # tiny; replicated
    (r"ssm/gate_norm$",              P("model")),
    (r"ssm/w_out$",                  P("model", None)),
    # norms (layer norm: a scale and a shift)
    (r"ln\d(/scale|/bias)?$",        P(None)),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


_MOE_EXPERT_RULES: Tuple[Tuple[str, P], ...] = (
    # expert-parallel: shard the EXPERT axis (moe_sharding='expert')
    (r"moe/(gate|up|down)$",         P("model", None, None)),
)

_MOE_EXPERT_PARALLEL = False


def set_moe_sharding(kind: str) -> None:
    """'tensor' (default) or 'expert' -- switches the moe weight rules."""
    global _MOE_EXPERT_PARALLEL
    _MOE_EXPERT_PARALLEL = (kind == "expert")


def _spec_for(path_s: str, ndim: int, divisible) -> P:
    rules = (_MOE_EXPERT_RULES + _RULES) if _MOE_EXPERT_PARALLEL else _RULES
    for pat, spec in rules:
        if re.search(pat, path_s):
            spec_t = tuple(spec)
            if len(spec_t) < ndim:                # scanned layer stack axes
                spec_t = (None,) * (ndim - len(spec_t)) + spec_t
            # drop 'model' sharding on dims not divisible by the axis size
            spec_t = tuple(
                (s if not (s == "model" and not divisible(i, spec_t)) else None)
                for i, s in enumerate(spec_t))
            return P(*spec_t)
    return P(*([None] * ndim))


def param_specs(params: PyTree, model_axis_size: int,
                prefix: Tuple = ()) -> PyTree:
    """PartitionSpec pytree matching ``params``.  ``prefix`` is prepended to
    every spec (e.g. ('clients',) for per-client stacked parameters)."""

    def one(path, leaf):
        path_s = _path_str(path)

        def divisible(i, spec_t):
            return leaf.shape[i] % model_axis_size == 0

        spec = _spec_for(path_s, leaf.ndim, divisible)
        return P(*(tuple(prefix) + tuple(spec)))

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# Cache sharding (decode/prefill)
# ---------------------------------------------------------------------------

def cache_specs(cache: PyTree, batch_axes, model_axis_size: int) -> PyTree:
    """Shard decode caches: batch dim over the data axes; the long cache
    seq dim over ``model`` (context-parallel cache); small leaves replicated.

    Layout conventions (see models/*.py):
      k/v    (L, B, S, kv, hd)   -> (None, batch, 'model', None, None)
      ckv    (L, B, S, r)        -> (None, batch, 'model', None)
      krope  (L, B, S, dr)       -> (None, batch, 'model', None)
      kpos   (L, S)              -> (None, 'model')
      conv   (L, B, W-1, ch)     -> (None, batch, None, 'model')
      state  (L, B, H, N, P)     -> (None, batch, 'model', None, None)
    """

    def one(path, leaf):
        name = _path_str(path).split("/")[-1]
        def div(dim_size, axis):
            if axis == "model":
                return dim_size % model_axis_size == 0
            return True

        if name in ("k", "v"):
            spec = [None, batch_axes, "model", None, None]
        elif name in ("ckv", "krope"):
            spec = [None, batch_axes, "model", None]
        elif name == "kpos":
            spec = [None, "model"]
        elif name == "conv":
            spec = [None, batch_axes, None, "model"]
        elif name == "state":
            spec = [None, batch_axes, "model", None, None]
        else:
            spec = [None] * leaf.ndim
        spec = spec[:leaf.ndim] + [None] * (leaf.ndim - len(spec))
        spec = [s if div(leaf.shape[i], s) else None
                for i, s in enumerate(spec)]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache)


# ---------------------------------------------------------------------------
# Activation sharding (sequence parallelism between blocks)
# ---------------------------------------------------------------------------

_ACT_SEQ_AXIS: Optional[str] = None
_SP_MLP = False


def set_activation_sharding(seq_axis: Optional[str],
                            sp_mlp: bool = False) -> None:
    global _ACT_SEQ_AXIS, _SP_MLP
    _ACT_SEQ_AXIS = seq_axis
    _SP_MLP = bool(sp_mlp and seq_axis)


def sp_mlp_axis() -> Optional[str]:
    """Axis for the explicit shard_map SP-MLP (None = disabled)."""
    return _ACT_SEQ_AXIS if _SP_MLP else None


def constrain_seq(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain a (..., S, D) residual-stream tensor to shard S over the
    configured axis (no-op when disabled or S not divisible).

    This is Megatron-style sequence parallelism: between blocks the
    residual lives sharded over 'model'; GSPMD inserts the all-gather
    before attention/MLP and the reduce-scatter after, replacing the
    full-tensor all-reduce and cutting the between-block activation
    footprint (and the remat stash) by the axis size.
    """
    if _ACT_SEQ_AXIS is None:
        return x
    spec = (None,) * (x.ndim - 2) + (_ACT_SEQ_AXIS, None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
