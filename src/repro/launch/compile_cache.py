"""JAX's persistent compilation cache for the repository's entry points.

Every entry point's ``main`` calls :func:`enable` first; importing this
module changes nothing.  The cache directory is the one
``JAX_COMPILATION_CACHE_DIR`` names, which JAX reads itself, or else the
fixed ``.jax_cache/`` at the checkout root.  The path is part of each entry's
key, so it is never built from a temporary name, a PID or the time.

An entry's key includes the program's metadata (its ops' scope names and
source lines): an executable loaded from the cache then carries the names
its own code gave it, which the device trace reports, and not those of the
code that first compiled an otherwise equal program.
"""
from __future__ import annotations

import os

import jax

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                    ".."))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
