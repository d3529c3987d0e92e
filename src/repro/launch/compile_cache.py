"""Persistent compilation cache for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives in `.jax_cache/` at the checkout root:
a fixed path, because the directory is part of what a cache hit matches.
Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
