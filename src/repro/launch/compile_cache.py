"""Where JAX keeps its persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and nothing
here overrides it. Otherwise the cache lives at one fixed path inside the
checkout (``<repo>/.jax_cache``, listed in ``.gitignore``), so a second run
from the same checkout reuses the first run's executables.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Point the persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
