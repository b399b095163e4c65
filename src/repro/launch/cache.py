"""JAX's persistent compilation cache, placed for the entry points.

The cache key includes the directory, so the directory must not move
between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself), and otherwise ``.jax_cache`` at the root of
the checkout. Only entry points call :func:`enable_compile_cache`; the
library and the tests never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
