"""JAX's persistent compilation cache, in one place for every entry point.

``$JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself,
so nothing is set in code.  Otherwise the cache lives at a fixed path in
the checkout, ``<repo>/.jax_cache/`` (listed in ``.gitignore``): the
path is part of the cache's key, so a per-process or temporary directory
would never hit, and a directory shared between hosts would hand one
machine executables compiled for another's CPU.
"""
from __future__ import annotations

import os

import jax

from repro.checkout import ROOT


def cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir`."""
    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
