"""JAX's persistent compilation cache for the entry points that compile
full-size programs (``chip_smoke.py`` and the ``repro.launch`` mains).

Call ``enable_compile_cache()`` from an entry point, never on import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
this sets nothing; otherwise the cache goes to ``<repo root>/.jax_cache``.
The path is part of each entry's key, so it is fixed: never temporary,
per-process or time-stamped.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
