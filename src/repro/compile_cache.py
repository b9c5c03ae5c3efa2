"""JAX persistent compilation cache placement.

Entry points (``chip_smoke.py``, the benchmark scripts) call
:func:`enable_compile_cache` once, before their first compile; importing
this module does nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
left to JAX and no other cache is configured in code.  Otherwise the
cache lives at a fixed directory inside the checkout (``.jax_cache``,
git-ignored): the directory is part of the cache key, so a path that
moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/...``
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
