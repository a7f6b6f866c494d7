"""Where JAX keeps its persistent compilation cache.

One helper, called by every entry point that compiles (``launch.serve``,
``chip_smoke.py``, the benchmarks).  A cache placed from outside wins: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
is changed.  Otherwise the cache is ``<checkout>/.jax_cache`` — a fixed path,
because the path is part of the cache key and a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
