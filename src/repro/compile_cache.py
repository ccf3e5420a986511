"""JAX's persistent compilation cache, configured once per entry point.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``) calls :func:`enable_compile_cache` before its first
compile; importing the package configures nothing.  Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads it itself and this sets
nothing.  Otherwise the cache lives in ``.jax_cache/`` at the checkout root:
a fixed path, because the path is part of what a cache entry is found by.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; the directory it uses."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
