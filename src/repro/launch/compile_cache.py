"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`setup_compile_cache` once, before their first compile.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what a later process
must find again, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's compilation cache at ``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
