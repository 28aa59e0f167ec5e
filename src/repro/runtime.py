"""Process set-up shared by every entry point.

``chip_smoke.py``, ``benchmarks.run`` and the ``examples/`` scripts call
:func:`enable_compile_cache` first, so one rule decides where compiled
programs persist.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (git-ignored): fixed, so a later process finds it
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing else is set.  Otherwise the cache goes to
    :data:`CACHE_DIR` inside the checkout — never a temporary, per-process
    or time-stamped path, which no later run could hit.
    """
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
