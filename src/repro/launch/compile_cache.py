"""JAX's persistent compilation cache, placed once per process.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
lives at ``<repo>/.jax_cache``, a fixed path resolved from this file's own
location, so a second run of the same program from the same checkout finds
the programs the first one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
