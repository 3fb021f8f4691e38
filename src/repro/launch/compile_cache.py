"""Persistent XLA compilation cache for the entry points.

Every entry point (``launch/serve.py``, ``launch/search.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
at the start of ``main()`` — never at import, so tests and library callers
run with the cache off.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
already read it and nothing else is set.  Otherwise the cache lives in
``<repo>/.jax_cache``: a fixed path, so a later run from the same checkout
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
