"""JAX's persistent compilation cache for the entry points.

``launch/serve.py``, ``launch/train.py`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed here. Otherwise the cache goes to ``<repo root>/.jax_cache``: a path
fixed by the checkout's location, because the path is part of the cache
key and a directory that moves never hits. Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
