"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (`chip_smoke.py`, `examples/*.py`, `benchmarks/run.py`) call
`enable_compile_cache()` before their first compile, so a second run of
the same shapes reads its programs back instead of compiling them again.
Library modules and the test suite never call it.

The directory is part of what a cache lookup matches, so it never moves:
``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that variable itself,
and nothing here overrides it), else ``<repo>/.jax_cache``, which git
ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
