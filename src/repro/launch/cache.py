"""Where JAX keeps its persistent compilation cache.

`$JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and wins.
Otherwise the entry points put the cache in `.jax_cache/` at the root of the
checkout: one fixed path, because the path is part of what a later run looks
up, so a directory named after a process, a time or a temporary name would
never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the cache directory; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
