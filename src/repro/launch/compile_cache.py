"""JAX persistent compilation cache: one fixed place per checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the entry points (``fl_train``,
``chip_smoke.py``, the benchmark harnesses) call
``enable_compile_cache()`` to keep the cache in ``<checkout>/.jax_cache``
(listed in ``.gitignore``).  The path is part of each cache entry's
key, so it is fixed: never built from a temp dir, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory
    and return that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
