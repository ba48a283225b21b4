"""JAX's persistent compilation cache, set up once by each entry point.

A run on a fresh machine compiles every step program again unless the
compiled executables are kept on disk.  ``enable_compile_cache`` is called
at the start of ``main`` of each entry point (``repro.launch.train``,
``benchmarks.run``, ``chip_smoke.py``) and never at import:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
  nothing else is configured;
* otherwise the cache lives at a fixed directory inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is fixed because it is
  part of what a later run looks up: a temp name would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
