"""JAX persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py`` and by the
``__main__`` blocks of ``launch/serve.py`` and ``launch/train.py`` (never
on import, never by the tests).  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already keeps its cache there and no other directory is set.
Otherwise the cache lives at ``.jax_cache/`` in the checkout (git-ignored):
a fixed path, since the directory is part of what makes a later run hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
