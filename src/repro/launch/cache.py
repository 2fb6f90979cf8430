"""JAX's persistent compilation cache, shared by the entry points.

A cold full-width compile costs minutes; with the cache on, a second run
of the same program in the same checkout reads its executables back.
"""
from __future__ import annotations

import os

import jax

#: the in-checkout default. A fixed path: the cache only pays off when
#: every run of this checkout looks in the same place.
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Called from an entry point's ``main()``, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
