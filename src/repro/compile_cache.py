"""JAX's persistent compilation cache, in one place.

Entry points that compile for the accelerator (``chip_smoke.py``,
``launch/serve.py``, the benchmarks) call ``enable_compile_cache()``
before their first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and this sets nothing. Otherwise the cache lives
at ``<repo>/.jax_cache/``: a fixed path inside the checkout, because
the path is part of what a later run must find again (never a tmp
name, a pid or the time). Tests do not call it.
"""
from __future__ import annotations

import collections
import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's own monitoring events -> the names compile_cache_counts() uses;
# JAX records "cache_misses" when it writes an entry
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_COUNTS: collections.Counter = collections.Counter()
_LISTENING = []


def _count(event: str, **_) -> None:
    if event in _EVENTS:
        _COUNTS[_EVENTS[event]] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on and start counting its use;
    returns the directory in use."""
    import jax
    if not _LISTENING:
        jax.monitoring.register_event_listener(_count)
        _LISTENING.append(_count)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def compile_cache_counts() -> dict:
    """Compiles that consulted the cache since ``enable_compile_cache``,
    how many of them it served, and how many entries were written."""
    return {k: _COUNTS[k] for k in ("requests", "hits", "writes")}
