"""Kernel runtime policy shared by every Pallas entry point.

Pallas TPU kernels compile only under Mosaic, on a TPU backend; every other
backend runs their bodies in the Pallas interpreter.  :func:`default_interpret`
is that rule and nothing else: interpret mode means exactly "not on a TPU".

Kernel entry points take ``interpret: bool | None = None`` and resolve
``None`` through this helper at trace time, so a bare call does the right
thing on any backend while tests can still pin either mode explicitly.
"""
from __future__ import annotations

import jax

__all__ = ["default_interpret", "resolve_interpret"]


def default_interpret() -> bool:
    """True when Pallas kernels run in interpret mode: on any backend but
    a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: "bool | None") -> bool:
    """``interpret`` if explicitly given, else :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)
