"""Where JAX keeps compiled programs between processes.

Entry points that compile the served path (``chip_smoke.py``,
``benchmarks/run.py``, ``examples/serve_cluster.py``) call
:func:`use_compile_cache` before their first compile.  Tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed fallback directory (the path is part of the cache key, so
#: it must not move between runs)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
