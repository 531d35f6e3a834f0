"""Where compiled programs persist between runs.

JAX's persistent compilation cache is keyed by, among other things, its
directory, so it must not move between runs: where `JAX_COMPILATION_CACHE_DIR`
is set, JAX uses it and nothing here overrides it; otherwise the cache is
the fixed directory `.jax_cache` at the repository root (git-ignored).
Entry points call `enable()` before their first compile.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
