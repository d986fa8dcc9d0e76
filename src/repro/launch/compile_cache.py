"""JAX's persistent compilation cache, placed from outside or in the checkout.

Entry points (the train CLI, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start; importing a module never turns
the cache on, and tests leave it off.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The checkout's own cache directory (listed in ``.gitignore``).  A fixed
#: path: a cache whose directory moves between runs is never hit.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
