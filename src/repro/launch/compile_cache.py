"""JAX's persistent compilation cache for the entry points.

Every entry point (`repro.launch.train.main`, `repro.launch.serve.main`,
`chip_smoke.py`) calls `enable_compile_cache()` before its first
compile.  Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache lives at the fixed path
`<checkout>/.jax_cache`: the directory is part of the cache key, so a
temporary or per-process path would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
