"""JAX's persistent compilation cache, set once by every entry point.

Each entry point (``chip_smoke.py``, ``examples/convergence_sweep.py``,
``launch/train.py``, ``benchmarks/run.py``, ``benchmarks/bench_regression.py``)
calls :func:`enable_compile_cache` before its first compile, so a second
run of the same program on the same device reuses the compiled
executables instead of recompiling the fused scan.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed location when the environment names none, so that a later run
#: finds what an earlier one cached
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives in ``<repo>/.jax_cache``
    (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
