"""Launchers: production meshes, dry-run, train/serve drivers."""
from __future__ import annotations

import os
from pathlib import Path

# Fixed, inside the checkout: the path is part of the cache key, so a
# directory that moved between runs would never hit.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.  Entry points call this before their first
    compile; importing this package changes nothing.  Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
