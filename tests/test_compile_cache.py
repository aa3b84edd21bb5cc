"""The persistent compile cache helper every entry point calls."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_config_is_untouched(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own reading of it stands
    (checked in a fresh process, where JAX reads the variable)."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    code = ("import jax; before = jax.config.jax_compilation_cache_dir; "
            "from repro.launch.compile_cache import enable_compile_cache; "
            "got = enable_compile_cache(); "
            "print(before, got, jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split() == [str(tmp_path)] * 3


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # git must never pick the cache up
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
