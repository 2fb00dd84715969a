"""Where JAX keeps its persistent compilation cache for this repo's scripts.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``enable()`` once, before they compile anything; no library module calls it
on import. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing is set here. Otherwise the cache goes to the fixed
``<repo>/.jax_cache``. The directory is part of each entry's key, so it
never carries a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
