"""Compile accounting: backend compiles, their seconds, and persistent
cache hits, from ``jax.monitoring`` events.  Register one per process;
``mark()`` snapshots the counts so a window can count its own compiles."""
from __future__ import annotations


class CompileLog:
    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return (self.compiles, self.seconds, self.cache_hits)

    def since(self, mark: tuple) -> dict:
        return {"compiles": self.compiles - mark[0],
                "compile_s": self.seconds - mark[1],
                "cache_hits": self.cache_hits - mark[2]}
