"""JAX's persistent compilation cache, placed at a fixed directory.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``, the
benchmark CLIs) call :func:`enable_compile_cache` once at start-up.
Library modules and tests never call it, so importing them leaves the
cache as JAX's own configuration has it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this helper sets no other directory. Otherwise the cache goes to
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is
part of the cache key, so it is never built from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``src/repro/launch/compile_cache.py`` → the checkout root.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it writes to."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
