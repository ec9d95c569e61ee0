"""JAX's persistent compilation cache for this repository's entry points.

The drivers (``launch.train``, ``launch.serve``, ``benchmarks.run``) and
``chip_smoke.py`` call ``enable_compile_cache()`` once at start-up; importing
a library module never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing.  Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later process must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
