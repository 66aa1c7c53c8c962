"""Host-phase spans of the harness, written into the profiler's own trace.

In a traced run every phase of the harness (traffic generation, submit,
``sched.step``, feed, pump, collect, finish, waiting for arrivals) is a
`jax.profiler.TraceAnnotation` named ``bench.<phase>``, so the trace
reduction can say what the host was doing in each idle gap of the device.
Untraced runs use a null context and pay nothing.
"""

from __future__ import annotations

import contextlib

PREFIX = "bench."
WINDOW = PREFIX + "window"


class Spans:
    def __init__(self, on: bool):
        self.on = bool(on)
        if self.on:
            import jax.profiler
            self._annotation = jax.profiler.TraceAnnotation

    def __call__(self, phase: str):
        if not self.on:
            return contextlib.nullcontext()
        return self._annotation(PREFIX + phase)
