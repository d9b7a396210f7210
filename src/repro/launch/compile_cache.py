"""JAX persistent compilation cache for the entry points.

``enable()`` is the first thing ``train_fedgbf``, ``serve_fedgbf`` and
``chip_smoke.py`` do.  The cache directory is part of the cache key, so it
never comes from a temporary name, a pid or the time:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
  nothing else is set here;
* otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
  (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
