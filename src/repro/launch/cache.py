"""JAX's persistent compilation cache, placed once per process by the entry
points (``chip_smoke.py``, ``repro.launch.train``, ``benchmarks.run``).

Never called at import, so the tests compile with JAX's own defaults.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Return the cache directory in use. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX already reads it and nothing is changed; otherwise the cache
    goes to ``<checkout>/.jax_cache``, a path fixed by the package's location
    (the path is part of the cache key, so it must not move between runs)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
