"""Collections of the interpreter's garbage collector inside a window: one
of the things a long step or a long gap between tokens can be."""
from __future__ import annotations

import gc
import time


class GcLog:
    def __init__(self):
        self.events, self._t = [], None     # (generation, seconds, started at)
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.events.append((info["generation"], time.perf_counter() - self._t, self._t))

    def close(self):
        gc.callbacks.remove(self._on)

    def summary(self):
        return {"collections": len(self.events),
                "full": sum(g == 2 for g, _, _ in self.events),
                "longest_ms": 1e3 * max((d for _, d, _ in self.events), default=0.0),
                "total_ms": 1e3 * sum(d for _, d, _ in self.events)}
