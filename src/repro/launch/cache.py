"""JAX's persistent compilation cache for the repo's entry points.

Compiling the served path for a TPU takes tens of seconds per chart; the
persistent cache lets a second run of the same program skip it. Called by
the entry points (``chip_smoke.py``, ``launch.serve_gp.main``,
``benchmarks.run``, the examples) — never by the tests or at import time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, because the path is part of what the
# cache is keyed on — a directory that moves never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads it
    (no other directory is set in code); otherwise the cache lives at
    ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
