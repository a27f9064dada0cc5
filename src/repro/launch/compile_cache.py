"""JAX's persistent compilation cache: one rule for every entry point.

``chip_smoke.py``, ``repro.launch.serve_auction`` and ``benchmarks/run.py``
call :func:`enable_compile_cache` before their first compile:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
  directory and no code names another;
* otherwise the cache goes to :data:`DEFAULT_DIR`, a fixed, gitignored path
  inside the checkout, so a second process finds the first one's entries;
* every compile is cached, the small scheduler kernels included.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["DEFAULT_DIR", "enable_compile_cache"]

#: the cache used where JAX_COMPILATION_CACHE_DIR is unset: <checkout>/.jax_cache
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
