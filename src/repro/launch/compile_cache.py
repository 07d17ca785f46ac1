"""JAX's persistent compilation cache for this repository's entry points.

An entry point (`chip_smoke.py`, `repro.launch.serve`, `benchmarks.run`)
calls `enable_compile_cache()` once, before its first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and nothing is
changed. Otherwise the cache goes to the fixed `<checkout>/.jax_cache`: the
path is part of the cache key, so a directory that moved between runs
would never hit. Importing this module changes no setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory JAX uses."""
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return jax.config.jax_compilation_cache_dir
