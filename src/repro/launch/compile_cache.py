"""Persistent XLA compilation cache for the entry points.

``enable()`` is called by the programs a user runs (``launch/serve.py``,
``chip_smoke.py``, ``benchmarks/bench_serving.py``), never at import.  JAX
keys a cache entry on, among other things, the directory it lives in, so
the directory never moves between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it; nothing
    here sets a directory;
  * otherwise: ``<checkout>/.jax_cache`` (git-ignored).  The variable is
    exported too, so engine worker processes share the same cache.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV)
    if not path:
        import jax
        path = str(DEFAULT_DIR)
        os.environ[ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path
