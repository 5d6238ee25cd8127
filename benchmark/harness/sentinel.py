"""Stops of the whole machine, seen from a second process.

The one-chip machine now and then stops every process on it for about a
tenth of a second.  Inside the benchmark's own process such a stop cannot
be told from a pause of the program's own (the collector, a compile, the
host between calls).  The sentinel is a child process that shares no
interpreter, lock or JAX runtime with the program: it sleeps 2 ms at a
time and records every wake-up that comes more than ``GAP_S`` after the
one before as a stop ``(previous wake-up, this wake-up)``.  A pause of the
program's process alone never delays it.

Its stamps are ``time.perf_counter()``, which on Linux is
``CLOCK_MONOTONIC`` and shared by every process of the machine, so a stop
lands on the same clock as the program's stamps with no conversion beyond
the clock's origin.

    with Sentinel() as sentinel:
        ...                 # the window
    sentinel.stops          # [(start_s, end_s), ...] on perf_counter
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# Far above a scheduler quantum (~4 ms) and above the 20-38 ms late wake-ups
# that a host whose cores the program keeps busy can give a sleeper; below
# every stop of the machine seen (105 ms and longer).
GAP_S = 0.080
STEP_S = 0.002

# The child imports nothing but the interpreter's own modules.  SIGTERM
# ends it (SIGINT may be ignored in a process started in the background).
# It leaves by itself once its parent is gone, so a harness that is killed
# leaves no process behind.
_CHILD = r"""
import json, os, signal, sys, time
gap, step, parent = float(sys.argv[1]), float(sys.argv[2]), int(sys.argv[3])
class Stop(Exception):
    pass
def stop(*_):
    raise Stop
stops, n = [], 0
signal.signal(signal.SIGTERM, stop)
try:
    prev = time.perf_counter()
    print(json.dumps({"ready": prev}), flush=True)
    while True:
        time.sleep(step)
        now = time.perf_counter()
        if now - prev > gap:
            stops.append((prev, now))
        prev = now
        n += 1
        if n % 250 == 0 and os.getppid() != parent:
            break
except Stop:
    pass
print(json.dumps({"stops": stops, "wakeups": n}), flush=True)
"""


class SentinelError(RuntimeError):
    pass


class Sentinel:
    """The child process; ``close()`` (or leaving the ``with``) stops it,
    waits for it to end and keeps its stops in ``self.stops``."""

    def __init__(self):
        self.stops: list[tuple[float, float]] = []
        self.wakeups = 0
        before = time.perf_counter()
        self._proc = subprocess.Popen(
            [sys.executable, "-S", "-c", _CHILD, str(GAP_S), str(STEP_S), str(os.getpid())],
            stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        line = self._proc.stdout.readline()
        after = time.perf_counter()
        try:
            ready = json.loads(line)["ready"]
        except (ValueError, KeyError, TypeError):
            self._end()
            raise SentinelError(f"the sentinel did not start: {line!r}")
        if not before <= ready <= after:
            self._end()
            raise SentinelError("the sentinel's perf_counter is not this process's clock: "
                                f"{ready} outside [{before}, {after}]")

    def close(self) -> list[tuple[float, float]]:
        if self._proc.poll() is None:
            self._proc.terminate()
        try:
            out, err = self._proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            self._end()
            raise SentinelError("the sentinel did not stop within 10 s")
        try:
            last = json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError):
            raise SentinelError(f"the sentinel printed no stops: {out[-300:]!r} {err[-300:]!r}")
        self.stops = [(float(a), float(b)) for a, b in last["stops"]]
        self.wakeups = int(last["wakeups"])
        return self.stops

    def _end(self):
        self._proc.kill()
        self._proc.communicate()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            self.close()
        else:               # the window failed: end the child, keep the first error
            self._end()
        return False


def spans_a_stop(a: float, b: float, stops) -> bool:
    """Whether the interval ``(a, b)`` overlaps any of ``stops``."""
    return any(a < end and b > start for start, end in stops)


def on_clock(stops, t0: float, begin: float, end: float) -> list[tuple[float, float]]:
    """The stops shifted onto a clock whose origin is ``t0`` (perf_counter
    seconds), those that overlap ``[begin, end]`` on that clock."""
    return [(a - t0, b - t0) for a, b in stops if a - t0 < end and b - t0 > begin]


def summary(stops, top: int = 8) -> dict:
    """What ``info`` prints: count, longest and total, and each stop's start
    on the window's clock with its length."""
    ms = [1e3 * (b - a) for a, b in stops]
    return {"count": len(stops), "longest_ms": max(ms, default=0.0),
            "total_ms": sum(ms, 0.0),
            "at": [[round(a, 3), round(d, 3)] for (a, _), d in
                   sorted(zip(stops, ms), key=lambda x: -x[1])[:top]]}
