"""XLA builds and persistent-cache loads, from JAX's monitoring events.

Copied from ``chip_smoke.py``'s ``CompileLog``: a backend compile that
follows a persistent-cache hit is a load, any other is a build.
"""
from __future__ import annotations

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        import jax
        self.builds, self.hits, self.written = [], 0, 0
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
            self._hit = True
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def _on_duration(self, event, duration, **_kw):
        if event == BACKEND_COMPILE:
            if not self._hit:
                self.builds.append(duration)
            self._hit = False

    def snapshot(self):
        return len(self.builds), self.hits, self.written

    def since(self, snap) -> dict:
        n, hits, written = snap
        new = self.builds[n:]
        return {"xla_builds": len(new), "build_s": sum(new),
                "cache_loads": self.hits - hits,
                "cache_writes": self.written - written}
