"""Where JAX keeps its persistent compilation cache.

``$JAX_COMPILATION_CACHE_DIR`` wins when it is set, and no other directory is
set then.  Otherwise the cache lives at one fixed path inside the checkout
(``<checkout>/.jax_cache``, git-ignored).  The path is never built from a
temp name, a pid or the time: a directory that moves between runs never
hits.  The launchers and ``chip_smoke.py`` call :func:`enable` first thing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The cache directory: the environment's, else the checkout's."""
    return os.environ.get(ENV) or str(CHECKOUT_CACHE)


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
