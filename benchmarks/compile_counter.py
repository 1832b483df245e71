"""XLA compilations through jax.monitoring (copied from chip_smoke.py).

Every compile request fires the backend-compile duration event; a request
the persistent cache answered also fires a cache-hit event, so `compiled`
is what the compiler really built. `seconds` sums the requests' durations
over all threads. Each request also leaves its interval on the host clock
(the event fires when the compile ends), for the idle-gap attribution."""

from __future__ import annotations

import threading
import time
from typing import List, Tuple


class CompileCounter:
    def __init__(self):
        import jax.monitoring as mon
        self._lock = threading.Lock()
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        self.intervals: List[Tuple[float, float]] = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            now = time.perf_counter()
            with self._lock:
                self.requests += 1
                self.seconds += secs
                self.intervals.append((now - secs, now))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.hits

    def snapshot(self) -> dict:
        with self._lock:
            return {"compiled": self.requests - self.hits,
                    "seconds": self.seconds}


_COUNTER = None


def compile_counter() -> CompileCounter:
    """One listener a process: jax.monitoring has no unregister."""
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER
