"""Where JAX keeps its persistent compilation cache, and what it did.

``enable()`` is for entry-point scripts (``chip_smoke.py``,
``benchmarks.run``); importing the library never calls it.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is set
here.  Otherwise the cache goes to ``<checkout>/.jax_cache`` — a fixed path,
because the path is part of the cache key: a directory that moves never hits.

``counts()`` reports cache hits, misses and backend-compile seconds seen since
``watch()`` registered its listeners, so a run can say whether its programs
came from the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax import monitoring

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COUNTS = {"hits": 0, "misses": 0, "compile_s": 0.0}
_watching = False


def _on_event(event: str, **_) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _COUNTS[key] += 1


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _COUNTS["compile_s"] += duration


def watch() -> None:
    """Start counting cache events and compile seconds (idempotent)."""
    global _watching
    if not _watching:
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    watch()
    return path


def counts() -> dict:
    """``{"hits", "misses", "compile_s"}`` since ``watch()``."""
    return dict(_COUNTS)
