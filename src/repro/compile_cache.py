"""JAX's persistent compilation cache, at a place that outlives the process.

Every new power-of-two (B, R) bucket the packer emits compiles the
estimation program once, so a fresh process compiles before its first
answer. `enable_compile_cache` lets the next process in the same checkout
read those programs back. The entry points that run the served path call
it first (`launch.serve_stats`, `launch.serve_fleet`, `benchmarks/run.py`,
`chip_smoke.py`); importing the package never does.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: derived from where the package lives, never from a
# temp name, a pid or the time, since the path is part of what makes a
# cache entry hit again.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    no other directory is set here. Otherwise the cache goes to
    `CHECKOUT_CACHE_DIR`. Either way every compile is kept, however short:
    the kernels compile in about a second, under JAX's default threshold.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
