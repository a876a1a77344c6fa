"""JAX's persistent compile cache for the entry points.

``enable()`` is called from the ``main()`` of the launchers and from
``chip_smoke.py`` — never at import time, so importing the package leaves
JAX's configuration alone.
"""
from __future__ import annotations

import os

import jax
from jax.experimental.compilation_cache import compilation_cache

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    keeps the cache there; nothing is set here. Otherwise the cache goes to
    ``<repo root>/.jax_cache``: a fixed path, because a cache whose
    directory moves between runs is never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
        # a compile before this call may have settled the cache as off
        compilation_cache.reset_cache()
    return DEFAULT_DIR
