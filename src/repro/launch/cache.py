"""Persistent XLA compilation cache for the entry points.

Process entry points (``chip_smoke.py``, the ``repro.launch`` CLIs'
``main()``, ``benchmarks/run.py``) call ``enable_compile_cache()`` once,
before their first compile; library code never does.  The directory is
fixed, so a later run finds what an earlier one cached:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

__all__ = ["compile_cache_dir", "enable_compile_cache"]

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/cache.py -> the checkout root, three levels above src/
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def compile_cache_dir() -> str:
    """The cache directory this process uses: ``$JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return it.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already
    uses that directory and no setting is changed."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
