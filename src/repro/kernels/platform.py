"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU.

Every kernel in this package resolves ``interpret=None`` through
:func:`interpret_mode`, so the rule lives in one place.  On a TPU every
kernel compiles through Mosaic; there is no interpreter or reference
fallback.  An explicit ``interpret=False`` exists for compiling a kernel
ahead of time for a described (not attached) chip from a CPU process.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["interpret_mode", "resolve_interpret"]


def interpret_mode() -> bool:
    """True exactly when JAX's default backend is the CPU."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` as given, or :func:`interpret_mode` when None."""
    return interpret_mode() if interpret is None else bool(interpret)
