"""Counts XLA compilations and persistent-cache hits while alive.

Copied from ``CompileCounter`` in the repository's ``chip_smoke.py``: it
listens to JAX's own monitoring events, so it counts every compilation of
the process, jitted programs and eager operations alike.
"""

from __future__ import annotations

import jax.monitoring as mon

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    def __init__(self) -> None:
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.n, self.seconds, self.cache_hits

    def close(self) -> None:
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
