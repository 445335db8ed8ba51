"""JAX's persistent compilation cache for the entry points.

Called at start-up by `chip_smoke.py`, `launch/train.py`, `launch/serve.py`
and `benchmarks/run.py`, never at library import.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing else is
set here; otherwise the cache lives at the fixed `<checkout>/.jax_cache`
(the path is part of the cache's key, so it must not move between runs).
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
