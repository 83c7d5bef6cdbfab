"""Where JAX keeps its persistent compilation cache for this repo's programs.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``benchmarks/run.py``) call :func:`use_compile_cache` once at startup; no
module calls it at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here. Otherwise the cache lives at
``<repo>/.jax_cache``: a fixed path, because a cache entry is only found
again under the directory it was written to.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
