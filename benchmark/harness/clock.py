"""The wall clock the benchmark hands the serving engine.

The engine's own ``measured`` clock is virtual: it adds up only the wall
time inside its jitted calls.  End-to-end numbers need the time a user
would see, so this clock reads ``time.perf_counter`` and really waits for
an arrival that is not due yet.  It also keeps the benchmark's spans: one
per call into the decode factories, by the engine's own kind names.
"""
from __future__ import annotations

import time

import jax

from paddle_tpu.serving.engine import EngineClock


class WallClock(EngineClock):
    def __init__(self, trace_window=None):
        super().__init__("measured")
        self.t0 = time.perf_counter()
        self.spans: list[tuple] = []        # (kind, start_s, end_s, units)
        self.oversleep_s: list[float] = []  # how late each wait woke
        self.slept_s = 0.0
        self.sleeps: list[tuple] = []       # (start_s, end_s) of each wait for an arrival
        self.trace_window = trace_window

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def advance_to(self, t: float):
        start = self.now()
        wait = t - start
        if wait > 0:
            time.sleep(wait)
            self.slept_s += wait
            end = self.now()
            self.sleeps.append((start, end))
            self.oversleep_s.append(end - t)

    def timed(self, kind, fn, units=None, cost=None):
        tw = self.trace_window
        if tw is not None:
            tw.tick(self.now())
        a = time.perf_counter()
        if tw is not None and tw.active:
            with jax.profiler.TraceAnnotation(f"bench:{kind}"):
                out = fn()
                jax.block_until_ready(out)
        else:
            out = fn()
            jax.block_until_ready(out)
        b = time.perf_counter()
        self.spans.append((kind, a - self.t0, b - self.t0, units))
        self.dev_wall += b - a
        self.t = b - self.t0
        return out
