"""Where JAX's persistent compilation cache lives: placed once, at start.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and nothing
is set here.  Otherwise the cache is kept at ``<repo>/.jax_cache``: a fixed
path, so a later process on the same checkout finds what an earlier one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Place the cache for this process; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
