"""JAX's persistent compilation cache for the launchers and the chip smoke.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory. Otherwise the cache goes to ``.jax_cache/`` at
the root of the checkout, found from this file's path: a fixed path, since
the path is part of what a later process must find again. Tests never call
:func:`enable`.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/launch/ -> root


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
