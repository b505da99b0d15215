"""Placement of JAX's persistent compilation cache.

The server (which is also chip_smoke.py's and the benchmark's only JAX
process) calls ``configure()`` before it builds its mesh, so a
restarted node finds
the executables its last run compiled instead of paying each batch
tier's compile on live traffic.  The cache directory
is part of each entry's key, so it is a fixed path: never a temp name,
a pid or a time.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure() -> str:
    """Return the compile-cache directory in force.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is touched — the operator placed the cache.  Otherwise the cache
    goes to ``.jaxcache/`` at the root of this checkout."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(checkout, ".jaxcache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
