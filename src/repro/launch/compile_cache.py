"""Persistent XLA compilation cache at a fixed place.

A cache only hits when a later run looks in the same directory, so the
directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` where it is set,
otherwise ``.jax_cache`` at the root of this checkout (gitignored).
Every program is cached, however fast it compiled: JAX's default skips
those under one second, so the Pallas kernels would recompile on every
run.  Entry points call ``enable_compile_cache()`` once at start-up;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
