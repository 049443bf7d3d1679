"""JAX's persistent compilation cache for this repo's entry points.

Entry points (``chip_smoke.py``, ``examples/``) call
:func:`enable_compile_cache` before their first compile; library modules
never do, so importing the package changes no JAX setting. A run on a
chip compiles every program afresh unless a cache from an earlier run
is found, and the cache's path is part of what it matches on: it stays
fixed, never a temporary, per-process or per-run directory.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout's root (this file is ``src/repro/launch/compile_cache.py``)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself
    and this sets nothing. Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
