"""Unified model API over all architecture families.

``Model`` is a functional wrapper (no state): ``init`` builds the parameter
pytree, ``forward``/``loss`` run full sequences (training), ``prefill`` +
``decode`` implement cached inference.  The audio/VLM frontends are the
assignment's sanctioned stub: precomputed frame/patch embeddings enter
through a learned projector and occupy the first ``frontend_len`` positions.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import blocks
from . import mla as mla_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import lecun_normal, norm, norm_init

PyTree = Any

__all__ = ["Model"]


def _dtype_of(cfg: ModelConfig):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[cfg.dtype]


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init

    def init(self, key) -> PyTree:
        cfg = self.cfg
        dt = _dtype_of(cfg)
        k_emb, k_stack, k_head, k_fe = jax.random.split(key, 4)
        params: PyTree = {
            "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                      * 0.02).astype(dt),
            "decoder": blocks.stack_init(k_stack, cfg, dt),
            "final_norm": norm_init(cfg.d_model, cfg.norm_type, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = lecun_normal(k_head,
                                             (cfg.d_model, cfg.vocab_size), dt)
        if cfg.frontend:
            params["frontend_proj"] = lecun_normal(
                k_fe, (cfg.frontend_dim, cfg.d_model), dt)
        return params

    # --------------------------------------------------------------- forward

    def _embed_inputs(self, params: PyTree, tokens: jnp.ndarray,
                      prefix_emb: Optional[jnp.ndarray]) -> jnp.ndarray:
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.cfg.frontend:
            if prefix_emb is None:
                raise ValueError(f"{self.cfg.name} requires prefix embeddings")
            pe = (prefix_emb.astype(x.dtype) @ params["frontend_proj"])
            x = jnp.concatenate([pe, x], axis=1)
        return x

    def _logits(self, params: PyTree, x: jnp.ndarray) -> jnp.ndarray:
        x = norm(x, params["final_norm"], self.cfg.norm_type,
                 self.cfg.norm_eps)
        head = (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        return (x @ head).astype(jnp.float32)

    def forward(self, params: PyTree, tokens: jnp.ndarray,
                prefix_emb: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """tokens (B, K) [, prefix (B, P, fdim)] -> (logits (B, P+K, V), aux)."""
        x = self._embed_inputs(params, tokens, prefix_emb)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, aux = blocks.stack_forward(self.cfg, params["decoder"], x,
                                      positions)
        return self._logits(params, x), aux

    def loss(self, params: PyTree, batch) -> jnp.ndarray:
        """batch = (tokens, targets[, prefix_emb]); targets (B, K) aligned so
        targets[:, i] is the next token after tokens[:, i]."""
        tokens, targets = batch[0], batch[1]
        prefix = batch[2] if len(batch) > 2 else None
        cfg = self.cfg
        P = cfg.frontend_len if cfg.frontend else 0

        def nll_of(logits, tgt):
            # logsumexp - one-hot contraction instead of log_softmax +
            # gather: keeps the (B, S, V) tensor reducible along a
            # vocab-sharded axis (the gather form forces an all-gather of
            # fp32 logits under SPMD).
            lse = jax.nn.logsumexp(logits, axis=-1)
            onehot = jax.nn.one_hot(tgt, cfg.vocab_size, dtype=logits.dtype)
            correct = jnp.einsum("bsv,bsv->bs", logits, onehot)
            return (lse - correct).sum()

        C = cfg.loss_chunk
        S = tokens.shape[1]
        if not (C and S % C == 0 and S > C):
            logits, aux = self.forward(params, tokens, prefix)
            logits = logits[:, P:]
            nll = nll_of(logits, targets) / targets.size
            return nll + cfg.router_aux_weight * aux

        # seq-chunked head+loss: the fp32 logits tensor never materializes
        # at (B, S, V) -- only (B, C, V) per scan step.
        x = self._embed_inputs(params, tokens, prefix)
        positions = jnp.arange(x.shape[1])
        x, aux = blocks.stack_forward(cfg, params["decoder"], x, positions)
        x = norm(x[:, P:], params["final_norm"], cfg.norm_type, cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        B = x.shape[0]
        nC = S // C
        xc = jnp.moveaxis(x.reshape(B, nC, C, -1), 1, 0)
        tc = jnp.moveaxis(targets.reshape(B, nC, C), 1, 0)

        def body(tot, xs):
            xi, ti = xs
            logits = (xi @ head).astype(jnp.float32)
            return tot + nll_of(logits, ti), None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (xc, tc))
        nll = total / targets.size
        return nll + cfg.router_aux_weight * aux

    # ----------------------------------------------------------------- cache

    def init_cache(self, batch: int, max_len: int) -> PyTree:
        """Decode cache sized ``max_len`` (pass min(context, window))."""
        cfg = self.cfg
        dt = _dtype_of(cfg)
        if cfg.family == "ssm":
            m = ssm_mod.make_ssm_cache(cfg, batch, cfg.n_layers, dt)
            return {"layers": {"conv": m["conv"], "state": m["state"]}}
        if cfg.family == "hybrid":
            n_groups = max(cfg.n_layers // cfg.hybrid_attn_every, 1)
            shared = attn_mod.make_kv_cache(cfg, batch, max_len, n_groups, dt)
            m = ssm_mod.make_ssm_cache(cfg, batch, cfg.n_layers, dt)
            return {"layers": {"conv": m["conv"], "state": m["state"]},
                    "shared": shared}
        maker = (mla_mod.make_mla_cache if cfg.mla
                 else attn_mod.make_kv_cache)
        n_scanned = cfg.n_layers - cfg.first_dense_layers
        out: PyTree = {"layers": maker(cfg, batch, max_len, n_scanned, dt)}
        if cfg.first_dense_layers:
            per = maker(cfg, batch, max_len, 1, dt)
            out["dense_layers"] = [
                jax.tree.map(lambda a: a[0], per)
                for _ in range(cfg.first_dense_layers)]
        return out

    # For ssm caches the layer axis already exists; normalize access:
    # cache["layers"] leaves all carry leading n_layers axis.

    def _scatter_ring(self, full: jnp.ndarray, W: int,
                      axis_seq: int = 2) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """full (..., S, ...) per-position values -> ring buffer (..., W, ...)
        plus kpos (L?, W).  Keeps the last min(S, W) positions."""
        S = full.shape[axis_seq]
        keep = min(S, W)
        start = S - keep
        tail = jax.lax.slice_in_dim(full, start, S, axis=axis_seq)
        pos = jnp.arange(start, S)
        if start % W == 0:
            # slots == arange(keep): identity layout.  Avoids a scatter
            # whose resharding forces SPMD involuntary full
            # rematerialization (the scatter result cannot keep the
            # seq-sharded layout of the KV entries).
            if keep == W:
                return tail, pos.astype(jnp.int32)
            pad = [(0, 0)] * full.ndim
            pad[axis_seq] = (0, W - keep)
            buf = jnp.pad(tail, pad)
            kpos = jnp.concatenate(
                [pos, jnp.full((W - keep,), -1, jnp.int32)])
            return buf, kpos.astype(jnp.int32)
        if keep == W:
            # cyclic layout: a roll, not a scatter (layout-preserving under
            # SPMD; scatters force involuntary full rematerialization)
            shift = start % W
            buf = jnp.roll(tail, shift, axis=axis_seq)
            kpos = jnp.roll(pos, shift).astype(jnp.int32)
            return buf, kpos
        slots = pos % W
        moved = jnp.moveaxis(tail, axis_seq, 0)
        buf_shape = (W,) + moved.shape[1:]
        buf = jnp.zeros(buf_shape, full.dtype).at[slots].set(moved)
        kpos = jnp.full((W,), -1, jnp.int32).at[slots].set(pos)
        return jnp.moveaxis(buf, 0, axis_seq), kpos

    def prefill(self, params: PyTree, tokens: jnp.ndarray,
                prefix_emb: Optional[jnp.ndarray] = None,
                max_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, PyTree]:
        """Run the prompt, build the decode cache.

        Returns (last-position logits (B, V), cache).  ``max_len`` sets the
        ring size (>= prompt length for exact full-context decode; window
        size for sliding-window archs)."""
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, prefix_emb)
        B, S, _ = x.shape
        if max_len is None:
            max_len = S if cfg.sliding_window is None else cfg.sliding_window
        positions = jnp.arange(S)
        x, entries = blocks.stack_prefill(cfg, params["decoder"], x, positions)
        logits = self._logits(params, x[:, -1:])[:, 0]

        cache: PyTree = {}
        if cfg.family == "ssm":
            cache["layers"] = {"state": entries["layers"]["state"],
                               "conv": entries["layers"]["conv"]}
            return logits, cache

        def ring_kv(e):
            """e: dict of full-seq entries with leaves (L, B, S, ...)."""
            out = {}
            kpos = None
            for name, v in e.items():
                buf, kpos = self._scatter_ring(v, max_len, axis_seq=2)
                out[name] = buf
            L = next(iter(e.values())).shape[0]
            out["kpos"] = jnp.broadcast_to(kpos, (L, max_len))
            return out

        if cfg.family == "hybrid":
            cache["layers"] = {"state": entries["layers"]["state"],
                               "conv": entries["layers"]["conv"]}
            cache["shared"] = ring_kv(entries["shared"])
            return logits, cache

        cache["layers"] = ring_kv(entries["layers"])
        if "dense_layers" in entries:
            cache["dense_layers"] = []
            for e in entries["dense_layers"]:
                one = ring_kv(jax.tree.map(lambda a: a[None], e))
                cache["dense_layers"].append(
                    jax.tree.map(lambda a: a[0], one))
        return logits, cache

    # ---------------------------------------------------------------- decode

    def decode(self, params: PyTree, cache: PyTree, token: jnp.ndarray,
               pos: jnp.ndarray) -> Tuple[jnp.ndarray, PyTree]:
        """One step: token (B,) int32, pos scalar int32 (absolute position of
        this token).  Returns (logits (B, V), new cache)."""
        x = jnp.take(params["embed"], token[:, None], axis=0)
        x, cache = blocks.stack_decode(self.cfg, params["decoder"], cache,
                                       x, pos)
        return self._logits(params, x)[:, 0], cache

    # ------------------------------------------------------------- utilities

    def generate(self, params: PyTree, tokens: jnp.ndarray, n_new: int,
                 prefix_emb: Optional[jnp.ndarray] = None,
                 max_len: Optional[int] = None) -> jnp.ndarray:
        """Greedy generation (host loop; testing/serving example)."""
        cfg = self.cfg
        B, K = tokens.shape
        P = cfg.frontend_len if cfg.frontend else 0
        prompt_len = K + P
        if max_len is None:
            win = cfg.sliding_window
            max_len = prompt_len + n_new if win is None else win
        logits, cache = self.prefill(params, tokens, prefix_emb, max_len)
        out = [jnp.argmax(logits, -1).astype(jnp.int32)]
        decode = jax.jit(self.decode)
        for i in range(n_new - 1):
            pos = jnp.asarray(prompt_len + i, jnp.int32)
            logits, cache = decode(params, cache, out[-1], pos)
            out.append(jnp.argmax(logits, -1).astype(jnp.int32))
        return jnp.stack(out, axis=1)

    def param_count(self, params: PyTree) -> int:
        import numpy as np
        return int(sum(np.prod(p.shape) for p in jax.tree.leaves(params)))
