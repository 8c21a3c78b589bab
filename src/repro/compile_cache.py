"""Where JAX's persistent compilation cache lives.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
directory is used and nothing here overrides it. Otherwise the cache goes
to a fixed directory inside the checkout, ``<checkout>/.jax_cache``. The
path is part of what makes an entry findable again, so it is never a
temporary, pid- or time-derived directory.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
