"""Where JAX's persistent compilation cache lives.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache`
first thing in ``main`` (never at import).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
configured here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, so consecutive runs of one checkout find each other's
executables.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
