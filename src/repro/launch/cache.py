"""JAX's persistent compilation cache at a fixed place.

The cache key includes the directory, so the directory must not move
between runs: it is ``<repo>/.jax_cache`` unless the environment names
another with ``JAX_COMPILATION_CACHE_DIR``, which JAX then reads itself.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable_compilation_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path.

    Call before the first compile.  Sets nothing when
    ``JAX_COMPILATION_CACHE_DIR`` is set."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
