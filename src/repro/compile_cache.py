"""JAX's persistent compilation cache, placed for the repository's entry
points.

Entry points that compile the full-width model call
:func:`enable_compile_cache` once, before their first compile.  Importing
this module changes nothing."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: a later run from the same checkout
# finds what an earlier one wrote
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is changed.  Otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
