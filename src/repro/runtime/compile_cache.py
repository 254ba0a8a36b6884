"""JAX's persistent compilation cache for the entry points.

A cold serving process compiles its megatick, chunked-prefill and admission
programs before the first token. The persistent cache lets the next process
with the same programs load them instead. The entry points call
``enable_compile_cache`` once at start-up; importing this module changes
nothing.
"""
from __future__ import annotations

import os

# <checkout>/src/repro/runtime/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that
    directory and nothing else is set. Otherwise the cache lives at the
    fixed path ``<checkout>/.jax_cache``, so that every later run from the
    same checkout finds what this one wrote.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
