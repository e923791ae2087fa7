"""Persistent XLA compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``repro.launch.sweep``) calls
:func:`enable_compile_cache` before its first compile, so a second run of
the same programs loads them instead of compiling them again.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads the variable itself; nothing else is set in code), else the fixed
``<checkout>/.jax_cache``. The path is part of what a later run must
find again, so it never holds a temp name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout-local default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
