"""The persistent compilation cache is placed from outside: the
environment's ``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed
``<repo>/.jax_cache`` — and only entry points turn it on. Its key holds the
programs' op metadata (named scopes), less the checkout's own path."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

KEY_FLAGS = (
    "jax_compilation_cache_dir",
    "jax_compilation_cache_include_metadata_in_key",
    "jax_hlo_source_file_canonicalization_regex",
    "jax_traceback_in_locations_limit",
)

# compiles each program named on the command line (``<scope>[@there]``)
# through the persistent cache and prints the cache's hits and misses; a
# program is a weighted mean from the library (ops in the checkout's files)
# under a named scope, called from one of two call sites
PROBE = """
import json, sys
import jax
import numpy as np
from repro import compile_cache
from repro.core import aggregation

compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
seen = {"hits": 0, "misses": 0}
def listen(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        seen["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        seen["misses"] += 1
jax.monitoring.register_event_listener(listen)

def program(scope):
    def f(x, w):
        with jax.named_scope(scope):
            return aggregation.cloud_model(x, w)
    return f

def here(f, x, w):
    return jax.jit(f).lower(x, w).compile()

def there(f, x, w):  # another call site of the same program
    return jax.jit(f).lower(x, w).compile()

out = []
for arg in sys.argv[1:]:
    scope, _, site = arg.partition("@")
    before = dict(seen)
    x, w = np.ones((4, 8), np.float32), np.arange(1.0, 5.0, dtype=np.float32)
    compiled = (there if site else here)(program(scope), x, w)
    compiled(x, w).block_until_ready()
    jax.clear_caches()
    # the executable, compiled or read back, names the program's own scope
    # on its ops (the weighting multiply here)
    scoped = f'op_name="jit(f)/{scope}/mul"' in compiled.as_text()
    out.append({**{k: seen[k] - before[k] for k in seen}, "scoped": scoped})
print(json.dumps(out))
"""


def _probe(src: Path, cache: Path, *scopes: str) -> list:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_COMPILATION_CACHE_DIR=str(cache), JAX_PLATFORMS="cpu", PYTHONPATH=str(src))
    out = subprocess.run(
        [sys.executable, "-c", PROBE, *scopes], env=env, capture_output=True, text=True,
        check=True, cwd=cache.parent,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture
def restore_cache_dir():
    before = {k: getattr(jax.config, k) for k in KEY_FLAGS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


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


def test_programs_that_differ_only_in_scopes_share_no_entry(tmp_path):
    src = Path(compile_cache.__file__).resolve().parents[1]
    got = _probe(src, tmp_path / "cache", "phase.a", "phase.b", "phase.a")
    # each program misses once when first compiled; the first is then read
    # back, so the cache works and the second's miss is its scope's doing
    assert got == [{"hits": 0, "misses": 1, "scoped": True}, {"hits": 0, "misses": 1, "scoped": True},
                   {"hits": 1, "misses": 0, "scoped": True}]


def test_one_program_from_two_call_sites_is_one_entry(tmp_path):
    src = Path(compile_cache.__file__).resolve().parents[1]
    got = _probe(src, tmp_path / "cache", "phase.a", "phase.a@there")
    assert got == [{"hits": 0, "misses": 1, "scoped": True}, {"hits": 1, "misses": 0, "scoped": True}]


def test_the_same_tree_from_another_directory_hits(tmp_path):
    src = Path(compile_cache.__file__).resolve().parents[1]
    ignore = shutil.ignore_patterns("__pycache__")
    for copy in ("one", "two"):
        shutil.copytree(src / "repro", tmp_path / copy / "src" / "repro", ignore=ignore)
    cache = tmp_path / "cache"
    assert _probe(tmp_path / "one" / "src", cache, "phase.a") == [{"hits": 0, "misses": 1, "scoped": True}]
    assert _probe(tmp_path / "two" / "src", cache, "phase.a") == [{"hits": 1, "misses": 0, "scoped": True}]


def test_enable_puts_metadata_without_the_checkout_into_the_key(monkeypatch, restore_cache_dir):
    import re

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    compile_cache.enable()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    pattern = jax.config.jax_hlo_source_file_canonicalization_regex
    here = str(compile_cache.ROOT / "src" / "repro" / "core" / "hierfavg.py")
    assert re.sub(pattern, "", here) == os.path.join("src", "repro", "core", "hierfavg.py")
    assert re.sub(pattern, "", "/elsewhere/site-packages/jax/x.py") == "/elsewhere/site-packages/jax/x.py"
