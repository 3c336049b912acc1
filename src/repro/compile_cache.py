"""Persistent XLA compilation cache for the program's entry points.

The serving launcher, the chip smoke and the benchmarks call
:func:`enable` before their first compile; importing the library never
does, so library users and the tests keep JAX's defaults.
"""
from __future__ import annotations

import os
import pathlib

import jax

# one fixed path inside the checkout: the directory is part of what lets
# a later run find an entry, so it never carries a pid or a timestamp
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory and no
    other is configured; otherwise the cache lives at
    :data:`DEFAULT_DIR`.  Every executable is cached however fast it
    compiled: a cold start jits each layer unit on its own, and those
    compile in well under JAX's one-second default threshold."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
