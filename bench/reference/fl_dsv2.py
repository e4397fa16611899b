"""Plain reference for the FL round on DeepSeek-V2-Lite, one chip's share
of each layer's experts.

Written from the paper (arXiv:2303.08988, Alg. 1 and eqs. 1-4) and the
published DeepSeek-V2-Lite (huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
``config.json`` and its modelling code), in ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  It imports nothing of the
program and takes only the inputs: the initial weights, the clients'
token windows, and the plan's columns (A, tau, active, eta).

The decoder, per layer (pre-norm RMSNorm at eps, sequential residual):
    attention, MLA with no q LoRA, 16 heads:
        q = h Wq                          (nope 128 | rope 64 per head)
        c, k_pe = h Wkv_a                 (latent 512 | rope 64, shared)
        k_nope, v = RMSNorm(c) Wkv_b      (nope 128 | v 128 per head)
        YaRN rotary on q's and k_pe's 64 rope dims (rotate-half):
          extra_i = theta^(-2i/64), inter_i = extra_i / factor,
          corr(b) = 64 ln(original / (2 pi b)) / (2 ln theta),
          low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)),
          ramp_i = clip((i - low) / (high - low), 0, 1),
          inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)
        softmax((q_nope k_nope + q_pe k_pe) / sqrt(192) * mscale^2 +
                causal) v Wo, mscale = 0.1 mscale_all_dim ln(factor) + 1
    feed-forward: SwiGLU in the leading dense layers; else the experts:
        s = softmax(h W_router) over all routed experts, I = top-k(s),
        y = scaling sum_{e in I, e held} s_e FFN_e(h) + FFN_shared(h)
        with each held expert evaluated on every token and weighted by
        its gate (0 where the token did not pick it), and the aux loss
        alpha sum_e f_e P_e, f_e = E / (S k) sum_t [e in I_t],
        P_e = mean_t s_te (per sequence: one sequence a batch here);
then a final RMSNorm and the untied head; the loss is the mean
next-token cross-entropy plus alpha times the layers' aux losses.

One round: for each client j in turn, T SGD steps on its windows from
the global x, its delta d_j = x_j - x (zero for a dropped client); then
    x <- x + sum_j c_j d_j,   c_j = (1 / m) sum_i tau_i active_i A_ij
which is eq. 3 followed by eq. 4 with m the sampled active clients.

Departures from the published model: the weights are the program's (a
seeded draw, not the checkpoint); the rope dims rotate as halves (i,
i + 32), where the published code pairs (2i, 2i + 1), the same attention
once each head's 64 rope columns of Wq and Wkv_a are permuted by
``[0, 2, ..., 62, 1, 3, ..., 63]``; each layer is recomputed in the
backward pass (``jax.checkpoint``), which changes no value.  The held
experts are ``[offset, offset + held)``, the absent ones add nothing.

``dtype`` and ``fault`` give the control and the faults that a cell's
limits must catch: ``dtype="bfloat16"`` runs the whole reference one
precision lower; ``fault`` is one of ``FAULTS``: ``frozen`` (no step),
``half_batch`` (the loss over the first half of each window),
``no_mixing`` (A = I), ``renorm_topk`` (the top-k gates renormalized to
sum to one) and ``no_yarn`` (plain rope frequencies and the plain
softmax scale).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.fl_lm import comm  # noqa: F401  (the round's counts)

FAULTS = ("frozen", "half_batch", "no_mixing", "renorm_topk", "no_yarn")


class Arch(NamedTuple):
    """What the layer equations read of the published config."""
    heads: int
    nope: int
    rope: int
    v: int
    latent: int
    eps: float
    top_k: int
    experts: int            # the router's width: every routed expert
    offset: int             # the first held expert
    renorm: bool
    scaling: float
    alpha: float
    inv_freq: Tuple[float, ...]
    scale: float

    @classmethod
    def of(cls, model: Dict[str, Any], fault: Optional[str] = None
           ) -> "Arch":
        dr = model["qk_rope_head_dim"]
        theta = float(model["rope_theta"])
        y = model["rope_scaling"]
        scale = (model["qk_nope_head_dim"] + dr) ** -0.5
        if fault == "no_yarn":
            inv_freq = theta ** (-np.arange(0, dr, 2) / dr)
        else:
            inv_freq = yarn_inv_freq(dr, theta, y)
            mscale = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1
            scale *= mscale * mscale
        return cls(model["num_attention_heads"], model["qk_nope_head_dim"],
                   dr, model["v_head_dim"], model["kv_lora_rank"],
                   float(model["rms_norm_eps"]),
                   model["num_experts_per_tok"], model["router_experts"],
                   model["expert_offset"],
                   bool(model["norm_topk_prob"]) or fault == "renorm_topk",
                   float(model["routed_scaling_factor"]),
                   float(model["aux_loss_alpha"]),
                   tuple(float(f) for f in inv_freq), float(scale))


def yarn_inv_freq(dim: int, theta: float, y: Dict[str, Any]) -> np.ndarray:
    """The YaRN frequencies of the module docstring (float64)."""
    factor, original = float(y["factor"]), y["original_max_position_embeddings"]

    def corr(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    extra = theta ** (-2.0 * i / dim)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def rotary(x, inv_freq):
    """Rotate-half rotary embedding of x (B, S, H, dr)."""
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32))
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(arch: Arch, h, p):
    B, S, _ = h.shape
    H, dn, dr, dv, r = arch.heads, arch.nope, arch.rope, arch.v, arch.latent
    q = (h @ p["wq"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rotary(q[..., dn:], arch.inv_freq)
    kv_a = h @ p["wkv_a"]
    c = rms_norm(kv_a[..., :r], p["kv_norm"], arch.eps)
    k_pe = rotary(kv_a[..., None, r:], arch.inv_freq)       # (B, S, 1, dr)
    kv = (c @ p["wkv_b"]).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btd->bhst", q_pe, k_pe[:, :, 0])
              ) * jnp.asarray(arch.scale, h.dtype)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, jnp.asarray(-jnp.inf, h.dtype))
    out = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(B, S, H * dv) @ p["wo"]


def swiglu(h, p):
    return (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]


def experts(arch: Arch, h, p):
    """The held experts' part of the layer and the shared experts; and
    the layer's aux loss (unweighted)."""
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    s = jax.nn.softmax(x @ p["router"].astype(x.dtype), axis=-1)
    top, ids = jax.lax.top_k(s, arch.top_k)
    if arch.renorm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = p["gate"].shape[0]
    # gate of each held expert for each token: 0 where it was not picked
    local = ids - arch.offset
    gates = jnp.zeros((B * S, held), x.dtype)
    for slot in range(arch.top_k):
        gates = gates + jnp.where(
            local[:, slot, None] == jnp.arange(held)[None, :],
            top[:, slot, None], jnp.zeros((), x.dtype))
    hidden = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["gate"])) \
        * jnp.einsum("td,edf->etf", x, p["up"])
    ys = jnp.einsum("etf,efd->etd", hidden, p["down"])
    y = arch.scaling * jnp.einsum("te,etd->td", gates, ys)
    y = y + swiglu(x, p["shared"])
    picked = jnp.sum(jax.nn.one_hot(ids, arch.experts, dtype=jnp.float32),
                     axis=(0, 1))
    f = arch.experts / (B * S * arch.top_k) * picked
    aux = jnp.sum(f * jnp.mean(s.astype(jnp.float32), axis=0))
    return y.reshape(B, S, d), aux


def layer(arch: Arch, x, p, moe: bool):
    x = x + attention(arch, rms_norm(x, p["ln1"], arch.eps), p["mla"])
    h = rms_norm(x, p["ln2"], arch.eps)
    if not moe:
        return x + swiglu(h, p["mlp"]), jnp.float32(0.0)
    y, aux = experts(arch, h, p["moe"])
    return x + y, aux


def logits(arch: Arch, p, tokens):
    """(logits, summed aux loss) of tokens (B, S)."""
    x = p["embed"][tokens]
    for dp in p["decoder"]["dense_layers"]:
        x, _ = jax.checkpoint(partial(layer, arch, moe=False))(x, dp)

    def body(carry, lp):
        x, aux = carry
        x, a = jax.checkpoint(partial(layer, arch, moe=True))(x, lp)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                               p["decoder"]["layers"])
    x = rms_norm(x, p["final_norm"], arch.eps)
    return x @ p["lm_head"], aux


def loss(arch: Arch, p, window, used=None):
    """Mean next-token cross-entropy of (B, S+1) token windows over the
    first ``used`` positions of each (all by default), plus alpha times
    the aux losses (one sequence a batch: DeepSeek's per-sequence form)."""
    inputs, targets = window[:, :-1], window[:, 1:]
    z, aux = logits(arch, p, inputs)
    picked = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    nll = jax.nn.logsumexp(z, axis=-1) - picked
    if used is None:
        ce = jnp.mean(nll)
    else:
        # causal: the first positions' outputs read only the first tokens
        keep = jnp.arange(nll.shape[-1]) < used
        ce = jnp.sum(jnp.where(keep, nll, 0)) / (jnp.sum(keep)
                                                 * nll.shape[0])
    return ce + arch.alpha * aux


def _add_client(arch, acc, x, windows, c, used, eta):
    """acc + c (x_T - x) after T SGD steps from x on windows (T, B, S+1)."""
    def step(p, window):
        g = jax.grad(partial(loss, arch, used=used))(p, window)
        return jax.tree.map(lambda q, gq: q - eta * gq, p, g), None

    final, _ = jax.lax.scan(step, x, windows)
    return jax.tree.map(lambda a, f, s: a + c * (f - s), acc, final, x)


_add = jax.jit(_add_client, static_argnums=0, donate_argnums=1)


@partial(jax.jit, static_argnums=0)
def _test_loss(arch, p, tests):
    """Mean over the held-out sequences (E, S+1) of each one's loss."""
    return jnp.mean(jax.lax.map(lambda w: loss(arch, p, w[None]), tests))


def run_rounds(model: Dict[str, Any], x0, batches: Sequence, rows: Sequence,
               test_set: np.ndarray, *, dtype: str = "float32",
               fault: Optional[str] = None) -> Tuple[List, List[float]]:
    """Run ``len(rows)`` rounds from ``x0`` (the program's params tree, host
    arrays) on the clients' token windows ``batches[t]`` (n, T, B, S+1);
    returns the params after each round (float32 host arrays) and the mean
    held-out loss on ``test_set`` (E, S+1) after each round."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    arch = Arch.of(model, fault)
    dt = jnp.dtype(dtype)
    n = np.asarray(batches[0]).shape[0]
    tests = jnp.asarray(np.asarray(test_set))
    x = jax.tree.map(lambda a: jnp.asarray(np.asarray(a, dt)), x0)
    params, losses = [], []
    with jax.default_matmul_precision("highest"):
        for window, row in zip(batches, rows):
            if fault != "frozen":
                A = np.eye(n) if fault == "no_mixing" else np.asarray(row.A)
                up = np.asarray(row.tau) * np.asarray(row.active)
                m = max(float(np.sum(up)), 1.0)
                c = (up @ A) / m * np.asarray(row.active)
                S = np.asarray(window).shape[-1] - 1
                used = jnp.asarray(S // 2 if fault == "half_batch" else S,
                                   jnp.int32)
                acc = jax.tree.map(jnp.zeros_like, x)
                for j in range(n):
                    acc = _add(arch, acc, x, jnp.asarray(window[j]),
                               jnp.asarray(c[j], dt), used,
                               jnp.asarray(row.eta, dt))
                x = jax.tree.map(lambda a, b: a + b, x, acc)
                del acc
            params.append(jax.tree.map(
                lambda a: np.asarray(a, np.float32), x))
            losses.append(float(_test_loss(arch, x, tests)))
    return params, losses
