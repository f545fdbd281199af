"""Persistent XLA compilation cache for the repo's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
itself reads it and this module sets no other directory; otherwise the
cache lives at the fixed path ``<repo>/.jax_cache`` (a moving path
would never hit: the directory is part of what a later run looks up).
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable", "DEFAULT_DIR"]

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
