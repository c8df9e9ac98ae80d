"""Where JAX keeps its persistent compilation cache.

JAX reads `JAX_COMPILATION_CACHE_DIR` itself; when it is set, nothing
here overrides it. Otherwise the cache goes to one fixed directory, since
the cache path is part of each entry's key and a moving directory never
hits. Entry points that run on the accelerator call `configure()`;
importing `repro` leaves the cache off, so CPU test runs stay silent.
"""
from __future__ import annotations

import os

import jax


def configure(default_dir: str) -> str:
    """Turn the persistent compilation cache on and return its directory:
    `JAX_COMPILATION_CACHE_DIR` when set, else `default_dir`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
