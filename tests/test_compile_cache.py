"""Where the persistent compilation cache lives (`repro.launch.compile_cache`).

`JAX_COMPILATION_CACHE_DIR`, where set, is JAX's own and nothing in code
overrides it; otherwise the cache is the fixed, git-ignored `.jax_cache`
at the repository root.
"""

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def keep_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, keep_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_uses_the_fixed_repo_directory(monkeypatch, keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path      # the same on every call
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_env_var_directory_receives_the_entries(tmp_path):
    """A compile in a fresh process lands in the env var's directory."""
    cache = tmp_path / "cache"
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import compile_cache\n"
        "compile_cache.enable()\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   cwd=tmp_path, timeout=120)
    assert any(cache.iterdir())
