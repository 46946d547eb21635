"""JAX's persistent compilation cache: where it lives, and what it saw.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) call ``use_persistent_cache()`` first, before any
compile.  Nothing calls it at import.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; left alone.
* Otherwise the cache goes to ``<checkout>/.jax_cache``.  The path is part
  of every entry's key, so it is fixed: never a temporary name, a process
  id or a time.

Every compile is cached, however short (the default skips compiles under
one second, which would hide every smoke-size program).  ``CompileCounter``
counts what XLA compiled and what the cache returned instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_persistent_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts, while open, the programs XLA compiled and the programs read
    back from the persistent cache instead::

        with CompileCounter() as cc:
            ...
        cc.compiled, cc.cache_hits
    """

    def __init__(self) -> None:
        self.requests = 0          # every compile, cache hits included
        self.cache_hits = 0

    @property
    def compiled(self) -> int:
        return self.requests - self.cache_hits

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT_EVENT:
            self.cache_hits += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.requests += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
