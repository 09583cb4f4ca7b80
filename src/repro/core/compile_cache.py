"""Where JAX's persistent compilation cache lives for this repo's programs.

The entry points (``launch.serve``, ``launch.train``, ``bench.cli`` and
``chip_smoke.py``) call ``use_persistent_cache()`` once from ``main``;
importing a library module never touches the cache.  The directory is part
of what a cached program is found by, so it is fixed: never built from a
temporary directory, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the in-checkout default (gitignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
