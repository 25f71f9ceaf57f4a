"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`enable_compile_cache` before their first compile;
importing this module changes nothing, and the tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and no
    other is used. Otherwise the cache lives at ``<repo root>/.jax_cache``:
    a fixed path, so every run of a checkout finds what earlier runs
    compiled.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
