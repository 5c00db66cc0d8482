"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/serve_throughput.py``) calls
``enable_compile_cache()`` before its first compile. If the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and this sets
nothing else. Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path (git-ignored), since the path is part of what a later run
must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
