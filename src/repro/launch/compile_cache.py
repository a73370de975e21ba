"""JAX's persistent compilation cache for the entry points.

``enable()`` is called at the start of each entry point's ``main`` (never
at import), so a test process that imports an entry point keeps whatever
cache configuration it already has. A cache directory is part of the
cache's key: one that moved between runs would never hit, so the default
is a fixed directory inside the checkout, with no temp name, PID or time
in it.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else at ``DEFAULT_DIR``, and
    return that directory."""
    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
