"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` from ``main`` (never at
import, so tests stay cache-free).  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already reads it and this sets nothing.  Otherwise the cache goes
to ``<checkout>/.jax_cache``: the directory is part of each entry's key, so
it is fixed, never derived from a temporary name, a pid or the time.

The key includes each program's metadata.  The named scopes that split a
profile by layer (``core/telemetry.py: SCOPES``) live in that metadata,
which JAX's default key strips: a program cached by a commit that named
no scopes, or other ones, would otherwise be served with its stale
``op_name``s.  Programs then compile anew when their source lines move.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
