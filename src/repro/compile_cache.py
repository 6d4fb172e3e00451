"""JAX's persistent compilation cache for the repository's entry points.

``chip_smoke.py`` and the ``benchmarks/`` scripts call :func:`enable`
before their first compile, so a second run on the same machine reuses the
serving bodies and kernels the first one compiled.  Library code and tests
never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set:
#: fixed, under the checkout (and listed in ``.gitignore``), so every run
#: of one checkout finds what the previous run stored
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
    nothing else is configured; otherwise the cache goes to
    :data:`DEFAULT_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
