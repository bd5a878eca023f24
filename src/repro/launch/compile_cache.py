"""Persistent compile cache for the command-line entry points.

A fresh process on a TPU host spends minutes compiling a full-width round;
JAX's persistent compilation cache keeps those executables on disk.  The
cache's directory is part of what makes an entry findable again, so it must
not move between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set
(JAX reads it itself), otherwise the cache lives at ``<checkout>/.jax_cache``.
Library code and tests never turn the cache on; only ``main`` functions do.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
