"""Where JAX keeps its persistent compilation cache — the one place that
decides it.  Every entry point (``chip_smoke.py``, ``launch/serve.py``,
the benchmarks) calls :func:`enable_compile_cache` before its first
compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX uses that directory, and no
  other is set.
* Otherwise: ``<checkout>/.jax_cache``, a fixed path (git-ignored).  The
  cache key includes the directory, so it is never built from a temp
  name, a pid or the time: a path that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at its directory and return
    it.  Call before the first compile: JAX fixes the cache when it first
    uses it."""
    import jax

    path = Path(os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
