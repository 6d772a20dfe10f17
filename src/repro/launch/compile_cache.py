"""JAX's persistent compilation cache, kept at one fixed place.

Every entry point that compiles (serve, train, the tuner, ``chip_smoke.py``)
calls :func:`enable` first, so processes of one checkout share compiled
programs.  The directory is part of what a later run must find again, so it
never moves: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable
itself, and nothing else is set here), otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
