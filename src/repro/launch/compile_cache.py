"""JAX's persistent compilation cache for the chip entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there
and nothing here overrides it. Otherwise the cache goes to the fixed
``.jax_cache/`` at the repo root: the directory is part of the cache
key, so it must not move between runs."""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
