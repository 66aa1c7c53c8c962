"""Python's garbage collector around the window.

`settle` (just before the load starts) collects once and freezes every object
that set-up made, the harness's schedule of chunks and sessions among them,
so that no collection inside the window scans them.  `GcWatch` times every
collection that runs inside the window; the info line reports how many ran
and their longest and total pause.
"""

from __future__ import annotations

import gc
import time


def settle() -> None:
    gc.collect()
    gc.freeze()


def unsettle() -> None:
    gc.unfreeze()


class GcWatch:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.pauses: list[tuple[int, float]] = []   # (generation, seconds)
        self._t = None

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = self.clock()
        elif self._t is not None:
            self.pauses.append((int(info["generation"]),
                                self.clock() - self._t))
            self._t = None

    def start(self) -> None:
        gc.callbacks.append(self._callback)

    def stop(self) -> None:
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)

    def summary(self) -> dict:
        ms = [1e3 * s for _, s in self.pauses]
        return {"collections": len(ms),
                "gen2": sum(g == 2 for g, _ in self.pauses),
                "max_ms": max(ms, default=0.0), "total_ms": sum(ms)}
