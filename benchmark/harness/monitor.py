"""Counts what JAX compiles and what its persistent cache spares.

``jax.monitoring`` reports every trace, lowering and backend compile (a hit
in the persistent cache still passes through ``backend_compile``) and the
cache's hits, misses and seconds saved.  A run reads the counters before and
after its window: compilations inside the window should be 0.
"""

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
}
_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileEvents:
    def __init__(self):
        import jax.monitoring

        self.counts = {"compilations": 0, "cache_hits": 0, "cache_misses": 0}
        self.seconds = {name: 0.0 for name in _DURATIONS.values()}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        name = _EVENTS.get(event)
        if name:
            self.counts[name] += 1

    def _on_secs(self, event, secs, **_):
        name = _DURATIONS.get(event)
        if name:
            self.seconds[name] += secs
            if name == "backend_compile_s":
                self.counts["compilations"] += 1

    def snapshot(self):
        return {**self.counts, **self.seconds}


def since(before, after):
    """Counter differences between two snapshots."""
    return {k: after[k] - before[k] for k in after}
