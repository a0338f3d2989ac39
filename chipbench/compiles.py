"""Counts the XLA programs a process compiles or loads from the persistent
cache. Copied from the bring-up check ``chip_smoke.py`` (``CompileCounter``)
so that the yardstick does not move with the program."""
from __future__ import annotations


class CompileCounter:
    """Backend compiles (a persistent-cache hit is one too, and fast), their
    seconds, and the persistent-cache hits, from ``jax.monitoring`` events.
    JAX keeps the listeners for the life of the process: make one."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self, phase: str) -> str:
        return (f"[compile] {phase}: programs={self.n} "
                f"seconds={self.seconds:.3f} persistent_cache_hits="
                f"{self.cache_hits}")
