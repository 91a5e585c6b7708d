"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`use_compile_cache` once, before their first compile; nothing
calls it at import.  The cache directory is part of the cache key, so it
must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing else is
set here), otherwise the fixed, gitignored ``.jax_cache/`` at the root of
the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
