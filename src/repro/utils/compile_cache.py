"""Where JAX's persistent compilation cache lives.

Every entry point (``chip_smoke.py``, ``repro.launch.train.main``, the
examples) calls :func:`enable_compile_cache` before its first compile, so a
second run of the same program on the same device loads its executables
instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed, inside the checkout (listed in .gitignore): a later run finds the
# cache again only at the same path
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads the
    variable itself and nothing is set here.  Otherwise the cache goes to
    ``.jax_cache/`` at the root of this checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
