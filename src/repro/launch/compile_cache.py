"""Persistent compilation cache for the entry points.

``enable_compile_cache()`` is called at the top of ``launch/serve.main``,
``launch/train.main`` and ``chip_smoke.py`` — never at import, so importing
the package leaves JAX's configuration alone.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path derived from the package location, because the path is part of
the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]   # <checkout>/src/repro/launch


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
