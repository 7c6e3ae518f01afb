"""Where JAX keeps its persistent compile cache.

A cache only hits when its directory stays put between runs, so the
directory is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
that variable itself) or one fixed directory inside the checkout,
``<checkout>/.jax_cache`` (git-ignored).  Entry points call
:func:`enable_compile_cache` before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: The fallback cache directory: ``.jax_cache`` at the checkout's root.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set it is used as it is and no
    other directory is set; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Takes effect only if called before the
    process's first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
