"""JAX's persistent compilation cache, placed where the caller says.

Entry points (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/engine_micro.py``) call ``use_compile_cache()`` once, before
their first compile. Library modules and tests never call it: importing
the package changes no JAX setting.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else is
configured. When it is unset the cache goes to one fixed directory inside
the checkout (``.jax_cache/``, listed in ``.gitignore``): the directory
is part of what a cached program is keyed on, so a path that moved
between runs would never hit.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Return the persistent-cache directory, pointing JAX at ``DEFAULT_DIR``
    first when ``JAX_COMPILATION_CACHE_DIR`` is unset (when it is set, JAX
    reads it itself and this changes nothing)."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
