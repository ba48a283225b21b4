"""The entry points' persistent compilation cache setup."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import (ENV_VAR, REPO_CACHE_DIR,
                                        enable_compile_cache)

REPO = Path(__file__).resolve().parents[1]


def _listing(path: Path) -> set:
    return set(os.listdir(path)) if path.is_dir() else set()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert REPO_CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_entries_land_in_the_env_dir_only(tmp_path):
    """A fresh process with the variable set writes its entries there,
    and nothing into the checkout's default directory."""
    repo_before = _listing(REPO_CACHE_DIR)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    env[ENV_VAR] = str(tmp_path)
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "import jax, jax.numpy as jnp\n"
            "jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)"
            "(jnp.ones(7)))\n")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert os.listdir(tmp_path)
    assert _listing(REPO_CACHE_DIR) == repo_before
