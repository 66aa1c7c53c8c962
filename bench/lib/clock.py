"""Compile seconds and compile counts, from jax's own monitoring events.

Adapted from the `CompileClock` of ``chip_smoke.py``: listeners on
`jax.monitoring` add up the time jax spends lowering and compiling, count
backend compiles and persistent-cache hits.  Tracing is left out: a jit
traced inside another jit reports its time nested in the outer one's.
"""

from __future__ import annotations

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in (LOWER, COMPILE):
            self.seconds += duration
        if event == COMPILE:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}
