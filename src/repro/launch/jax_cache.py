"""Persistent compilation cache for the entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when it is set nothing is
set here. Otherwise the entry points keep the cache at one fixed path in the
repository, ``<repo>/.jax_cache``, where the next run finds the compiled
programs again: a directory whose name holds a temp name, a pid or a time is
never found again. Entry points call ``use_compile_cache`` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent cache at the repo's cache directory unless
    ``JAX_COMPILATION_CACHE_DIR`` is set. Returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return jax.config.jax_compilation_cache_dir
