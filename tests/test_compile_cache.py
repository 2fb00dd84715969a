"""The persistent compilation cache is placed from outside: the
environment's ``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed
``<repo>/.jax_cache`` — and only entry points turn it on."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_dir_wins_and_nothing_is_set(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(Path(compile_cache.__file__).resolve().parents[2] / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable() == want  # no pid, time or temporary name in it


def test_importing_the_library_sets_no_cache():
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    code = (
        "import jax, repro, repro.core, repro.fed, repro.kernels, repro.compile_cache; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, check=True
    )
    assert out.stdout.strip() == "None"
