"""Seconds jax spent tracing, lowering and compiling (or loading from
the persistent cache), and how many executables it asked the backend
for, between two readings — so a compile inside the measured window
shows instead of passing for the system's time."""

from __future__ import annotations

import threading


class CompileMeter:
    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._seconds, self._programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if name.startswith("/jax/core/compile/"):
            with self._lock:
                self._seconds += secs
                self._programs += name.endswith("backend_compile_duration")

    def take(self):
        with self._lock:
            out = (self._seconds, self._programs)
            self._seconds, self._programs = 0.0, 0
        return out
