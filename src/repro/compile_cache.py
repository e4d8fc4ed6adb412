"""JAX's persistent compilation cache for the repo's entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``benchmarks/run.py`` and the two streaming examples before their first
compile, never on import and never from tests.  Every epoch program then
compiles once per cache directory instead of once per process.

* If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  helper sets nothing.
* Otherwise the cache lives at ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed on purpose: it is part of the cache
  key, so a per-run temporary directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
