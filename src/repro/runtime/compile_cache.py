"""JAX's persistent compilation cache, kept at one fixed place.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``python -m repro.bench``) call
:func:`enable_compile_cache` before their first compile. A cache entry
is found again only under the same directory, so the directory never
depends on the process, the time or a temporary path.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own
    and nothing is set here. Otherwise the cache sits in ``.jax_cache``
    at the root of the checkout.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
