"""Counts compiles through JAX's monitoring events.

Copied from ``chip_smoke.py::Compiles`` (fresh compiles and persistent
cache hits), with one counter added: ``backends``, every backend
compile request, whether the executable was built fresh or loaded from
the persistent cache.  A jitted call served from the process's own
cache emits none of these events, so a window in which ``backends``
does not move compiled nothing.
"""

from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class Compiles:
    def __init__(self):
        import jax

        self.misses = self.hits = self.backends = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            self.backends += 1
