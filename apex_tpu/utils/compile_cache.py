"""Where JAX's persistent compilation cache lives for this checkout.

The cache directory is part of every entry's key, so it must not move
between runs: either the operator places it (``JAX_COMPILATION_CACHE_DIR``,
which jax reads itself) or it sits at one fixed path inside the checkout.
"""

from __future__ import annotations

import os

import jax

#: the fixed in-checkout location (git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set nothing is configured here —
    jax already reads the variable; otherwise the cache is placed at
    :data:`DEFAULT_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
