"""Batched serving CLI: prefill + decode loop on a (debug) mesh.

Demonstrates the production inference path at CPU scale: the same
``make_prefill_step`` / ``make_decode_step`` the 512-chip dry-run lowers,
executed for real with a reduced architecture on host devices.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \\
      --batch 4 --prompt-len 32 --new-tokens 16 [--devices 8]
"""

import os
import sys

if __name__ == "__main__" and "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n}")

import argparse                                                 # noqa: E402
import time                                                     # noqa: E402
from dataclasses import replace                                 # noqa: E402

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro.configs import arch_names, get_config                # noqa: E402
from repro.fl import make_decode_step, make_prefill_step       # noqa: E402
from repro.launch.mesh import make_debug_mesh                   # noqa: E402
from repro.launch.cache import enable_compile_cache             # noqa: E402
from repro.models.model import Model                            # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-7b", choices=arch_names())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    n_dev = len(jax.devices())
    if n_dev >= 4:
        mesh = make_debug_mesh((max(n_dev // 4, 1), 4), ("data", "model"))
    else:
        mesh = make_debug_mesh((1, n_dev), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)}")

    cfg = replace(get_config(args.arch, reduced=True), vocab_size=512)
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    print(f"{cfg.name}: {model.param_count(params):,} params")

    rng = np.random.default_rng(args.seed)
    B, K = args.batch, args.prompt_len
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, K)),
                          jnp.int32)
    prefix = None
    if cfg.frontend:
        prefix = jnp.asarray(rng.standard_normal(
            (B, cfg.frontend_len, cfg.frontend_dim)), jnp.float32)

    P = cfg.frontend_len if cfg.frontend else 0
    cache_len = P + K + args.new_tokens
    baxes = ("data",) if B % mesh.shape["data"] == 0 else None
    prefill = make_prefill_step(cfg, mesh, baxes, cache_len=cache_len)
    decode = make_decode_step(cfg, mesh, baxes)

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        if prefix is not None:
            logits, cache = prefill(params, prompts, prefix)
        else:
            logits, cache = prefill(params, prompts)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        out = [jnp.argmax(logits, -1).astype(jnp.int32)]
        t0 = time.perf_counter()
        for i in range(args.new_tokens - 1):
            pos = jnp.asarray(P + K + i, jnp.int32)
            logits, cache = decode(params, cache, out[-1], pos)
            out.append(jnp.argmax(logits, -1).astype(jnp.int32))
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0

    toks = np.stack([np.asarray(o) for o in out], axis=1)
    print(f"prefill: {B}x{K} tokens in {t_prefill * 1e3:.1f} ms")
    print(f"decode:  {args.new_tokens - 1} steps x {B} seqs in "
          f"{t_decode * 1e3:.1f} ms "
          f"({(args.new_tokens - 1) * B / max(t_decode, 1e-9):.0f} tok/s)")
    for b in range(min(B, 2)):
        print(f"  seq{b}: {toks[b].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
