"""The benchmark's own tests run on the CPU, at tiny sizes, in minutes:

    python -m pytest bench/tests
"""
import os
import pathlib
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


@pytest.fixture(scope="session", autouse=True)
def shared_compile_cache(tmp_path_factory):
    """Share compiled programs between the tests' runs (each run builds its
    programs anew, and most of them are alike)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(tmp_path_factory.mktemp("jax-cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
