"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile for the chip (``chip_smoke.py``, the benchmark
mains) call ``enable_compile_cache()`` before their first compile.  The
cache directory is part of the cache's key, so it must not move between
runs: ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, so nothing is set here), else the repository's
``.jax-cache`` — never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the repository root (this file is src/repro/launch/compile_cache.py)
REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(REPO_ROOT / ".jax-cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
