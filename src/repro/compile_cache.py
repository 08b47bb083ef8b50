"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``repro.launch.serve`` and
``benchmarks/bench_query_engine.py`` call :func:`enable_compile_cache` before
they compile anything, so a second run of the same programs loads them from
disk instead of recompiling (minutes on a TPU for the fused query step).

Where the cache lives:

* ``$JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads the variable
  itself, and nothing else is configured here;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path
  is fixed on purpose: a per-run, temp or pid-derived directory would never
  be hit again.

:func:`cache_events` counts hits and misses, so a run can show it used the
cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_ENV", "CHECKOUT_CACHE_DIR", "enable_compile_cache",
           "cache_events"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def cache_events() -> dict:
    """Persistent-cache hits and misses seen since enable_compile_cache()."""
    return dict(_counts)
