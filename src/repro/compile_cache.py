"""Placement of JAX's persistent compilation cache for the entry points.

Called by the command-line entry points (``chip_smoke.py``, the benchmark
runner, the codec and store CLIs) -- never at library import, so importing
``repro`` leaves JAX's configuration alone.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already keeps its cache there, and
  nothing else is configured.
* Otherwise the cache lives at ``<repo root>/.jax_cache``.  The path is part
  of the cache key, so it is fixed: no temporary name, pid or timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
ENV = "JAX_COMPILATION_CACHE_DIR"

_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _counts["misses"] += 1


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


def stats() -> dict:
    """Persistent-cache hits and misses seen since :func:`enable`."""
    return dict(_counts)
