"""Platform-aware Pallas execution-mode selection.

The kernels in this package carry an ``interpret`` knob: ``True`` runs
the Pallas interpreter (any backend, used for CPU validation), ``False``
lowers to a compiled Mosaic kernel (TPU).  Callers default the knob to
``None``, which resolves here: compiled on TPU, interpreted elsewhere,
overridable per-process via the ``REPRO_PALLAS_INTERPRET`` environment
variable or per-call by passing ``interpret=`` explicitly.

``REPRO_PALLAS_INTERPRET`` accepts ``1/true/interpret`` (force the
interpreter, e.g. to debug a miscompile on TPU) and ``0/false/compiled``
(force compiled lowering).  Any other value raises at first kernel
dispatch.  ``chip_smoke.py`` refuses to run when the interpreter is
selected on the chip.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = ["default_interpret", "resolve_interpret"]

_ENV = "REPRO_PALLAS_INTERPRET"
_TRUE = ("1", "true", "yes", "interpret")
_FALSE = ("0", "false", "no", "compiled")


def default_interpret() -> bool:
    """Pallas execution mode for this process: ``False`` (compiled) on
    TPU, ``True`` (interpreter) on every other backend, unless the
    ``REPRO_PALLAS_INTERPRET`` environment variable overrides."""
    env = os.environ.get(_ENV)
    if env is not None:
        v = env.strip().lower()
        if v in _TRUE:
            return True
        if v in _FALSE:
            return False
        raise ValueError(
            f"{_ENV}={env!r}: expected one of {_TRUE + _FALSE}")
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a caller's ``interpret`` argument: an explicit bool wins;
    ``None`` defers to ``default_interpret()``."""
    return default_interpret() if interpret is None else bool(interpret)
