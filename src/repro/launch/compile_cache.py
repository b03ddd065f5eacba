"""JAX's persistent compilation cache for the entry points.

Every entry point (the partition and partition-service CLIs, and
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and
nothing is set here. Otherwise the cache goes to one fixed directory inside
the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what lets a later run find the entries, so it is never built from a
temp name, a pid or the time. On the CPU backend the cache stays off: CPU
executables read back from it trip XLA's host-feature check, and nothing
the chip needs is compiled there.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory in use (None on
    the CPU backend)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
