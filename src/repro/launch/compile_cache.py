"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` first in ``main()`` (never at
import). When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is set. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of what a
later process must find again.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
