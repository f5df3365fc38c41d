"""JAX's persistent compilation cache for the repo's entry points.

Called once at startup by ``chip_smoke.py``, ``repro.launch.fedtrain``
and ``benchmarks.run`` — never at import, so importing the package does
not touch JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the directory is part of the
# cache's key — a directory that moves between runs never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured. Otherwise the cache lives at
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
