"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
`enable_compile_cache()` once at start-up; nothing calls it at import.
A set ``JAX_COMPILATION_CACHE_DIR`` is honoured and no other directory
is used.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the path is part of the cache key, so a directory that
moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the repo's."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()` and
    return that directory."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
