"""Persistent XLA compilation cache at a fixed, caller-chosen place.

A cold process on an accelerator host spends much of its first call
compiling. JAX's persistent cache keeps compiled programs on disk, keyed
in part by the cache path, so a directory that moves between runs never
hits: the path must not depend on a temporary name, a process id or the
time.

``JAX_COMPILATION_CACHE_DIR``, when set, already points the cache
somewhere; JAX reads it itself and nothing is set here. Otherwise the
cache goes to ``<root>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent compilation cache on; return its directory.

    Call before the first compile. ``root`` is a fixed directory (a
    checkout's root); the cache is ``<root>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set, which then wins untouched.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
