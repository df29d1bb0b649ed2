"""JAX's persistent compilation cache for the launchers and chip_smoke.py.

Called from entry points only, never at library import.  The cache's
path is part of what it matches on, so it must not move between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set -> JAX reads it itself and nothing
  here sets another directory;
* unset -> ``.jax_cache/`` at the root of the checkout (git ignores it).
"""
from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
