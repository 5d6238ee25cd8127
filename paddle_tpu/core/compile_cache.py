"""Where the on-chip entry points keep JAX's persistent compile cache.

Called by ``chip_smoke.py`` and ``bench.py`` — never at package import,
so test runs do not fill the checkout with CPU programs. The directory
is part of the cache key, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this code
sets nothing; otherwise the cache lives at one fixed, git-ignored path
inside the checkout that every process of a run shares.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_entries(path: str) -> int:
    """Number of cached programs under ``path`` (0 when absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
