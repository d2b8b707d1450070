"""Where JAX's persistent compilation cache lives, and what compiled.

The launchers and ``chip_smoke.py`` call :func:`enable_compile_cache`
first thing in ``main`` (never at import).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no directory
is configured here.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, so consecutive runs of one
checkout find each other's executables.

``COMPILE_COUNTS`` counts, from the moment :func:`count_compiles` (which
:func:`enable_compile_cache` calls) registers its listeners, every program
handed to the backend and what that cost:

    backend_compiles    programs compiled or loaded from the cache
    backend_compile_s   their seconds, loads included
    cache_hits          programs loaded from the persistent cache
    cache_misses        programs compiled and written to it
    cache_retrieval_s   seconds spent reading the cache

A caller reads it before and after a stretch of work and takes the
difference; ``backend_compiles - cache_hits`` compiled anew.
"""

from __future__ import annotations

import collections
import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

COMPILE_COUNTS: collections.Counter = collections.Counter()

_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}
_DURATIONS = {
    "/jax/core/compile/backend_compile_duration": ("backend_compiles",
                                                   "backend_compile_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec": (None,
                                                        "cache_retrieval_s"),
}
_listening = False


def _on_event(event: str, **_):
    key = _EVENTS.get(event)
    if key is not None:
        COMPILE_COUNTS[key] += 1


def _on_duration(event: str, seconds: float, **_):
    keys = _DURATIONS.get(event)
    if keys is not None:
        count, total = keys
        if count is not None:
            COMPILE_COUNTS[count] += 1
        COMPILE_COUNTS[total] += seconds


def count_compiles():
    """Register the listeners that feed ``COMPILE_COUNTS`` (once)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def enable_compile_cache() -> str:
    """Turn the persistent cache on and count compiles; returns the
    directory in use.  The cache is keyed on the programs' metadata too:
    an executable keeps the op names (the program's scopes) of the code
    that compiled it, and a profile of a run names what that run's code
    traced, never what an older or another checkout's did."""
    count_compiles()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
