"""The one persistent compilation cache every entry point uses.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
nothing here overrides it. Otherwise the cache lives at a fixed directory
inside the checkout (``.jax_cache/``, git-ignored): a temporary or
per-process directory would start empty on every run and never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
