"""Where JAX keeps compiled programs between processes.

Entry points call :func:`use_compile_cache` once, before their first
compile; library modules never do.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is read by JAX itself and nothing else is set here.  Otherwise the
cache goes to a fixed ``.jax_cache`` at the root of the checkout: the
path is part of the cache key, so a directory that moved would never
hit.
"""

from __future__ import annotations

import os

#: the checkout root (this file is ``<root>/src/repro/launch/compile_cache.py``)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
