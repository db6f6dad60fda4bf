"""Persistent XLA compilation cache at a path that can be set from outside.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set.  Otherwise the cache
lives in ``.jax_cache/`` at the root of the checkout (git-ignored).  The
path is part of every entry's key, so it is fixed: never a temp name, a
process id or a time.  Entry points call ``enable_compile_cache()`` before
their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the path in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    os.makedirs(path, exist_ok=True)   # JAX does not create it
    jax.config.update("jax_compilation_cache_dir", path)
    return path
