"""Where JAX keeps its persistent compilation cache for this repo's scripts.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``enable()`` once, before they compile anything; no library module calls it
on import. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and no directory is set here. Otherwise the cache goes to the fixed
``<repo>/.jax_cache``. The directory is part of each entry's key, so it
never carries a temporary name, a pid or a time.

Op metadata is part of the key too. JAX leaves it out by default, and
then two programs that differ only in their ``jax.named_scope``s share one
entry: whichever was compiled first hands its executable, and its scopes, to
the other, and the other's traces name the wrong phases or none. With the
metadata in the key, the checkout's own path is cut from every source file
name first, so the same tree run from another directory still hits; and
each op's location keeps only its innermost frame (its own source line,
not the call stack above it), so one program traced from two call sites is
still one entry.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = ROOT / ".jax_cache"


def _checkout_prefix() -> str:
    """A regex for the checkout's directory at the head of a source file
    name, as resolved and as imported (they differ under a symlink)."""
    roots = {str(ROOT), os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))}
    return "^(?:" + "|".join(re.escape(r + os.sep) for r in sorted(roots)) + ")"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", _checkout_prefix())
    jax.config.update("jax_traceback_in_locations_limit", 1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
