"""Compile events from `jax.monitoring`: seconds spent tracing, lowering
and compiling, the persistent cache's hits and misses, and how many
programs were compiled or fetched from the cache (a copy of the program's
``chip_smoke.SetupClock``, with that count added)."""
from __future__ import annotations


class SetupClock:
    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, name, secs, **_):
        if name in self._DURATIONS:
            self.seconds += secs
        if name == self._BACKEND:
            self.backend_compiles += 1

    def _on_count(self, name, **_):
        kind = name.removeprefix("/jax/compilation_cache/cache_")
        if kind in self.cache:
            self.cache[kind] += 1

    @property
    def programs(self) -> int:
        """Programs compiled or loaded from the persistent cache so far."""
        return self.backend_compiles + self.cache["hits"]
