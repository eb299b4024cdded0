"""Counts XLA compiles, per jitted function, from JAX's own monitoring
events; persistent-cache hits are counted apart (they do not compile)."""

from __future__ import annotations


class CompileCounter:
    def __init__(self):
        self.compiles: dict = {}
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[fun_name] = self.compiles.get(fun_name, 0) + 1
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> int:
        return sum(self.compiles.values())
