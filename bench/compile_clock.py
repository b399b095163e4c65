"""Compile time and compile count, as JAX reports them."""

from __future__ import annotations


class CompileClock:
    """Sums the backend compile time JAX reports, and counts cache hits."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}

    def since(self, before: dict) -> dict:
        now = self.reading()
        return {k: now[k] - before[k] for k in now}

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
