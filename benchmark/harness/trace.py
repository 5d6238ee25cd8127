"""The device trace of a short stretch of the window, and its reduction.

``TraceWindow`` starts and stops JAX's profiler from the measuring thread
over a stretch of the window that ``serving_stretch`` places from the
schedule of arrivals alone, before the window starts: on seconds in which
work is certain whatever the program's speed.  ``reduce_xplane`` turns the
profiler's ``.xplane.pb`` into the few tables the per-layer readers use:
busy seconds (the union of the intervals in which an operation ran,
averaged over the chips), seconds per (program, operation), and the idle
gaps by what the host was doing, the engine's waits for the next arrival
named apart.
"""
from __future__ import annotations

import glob
import os
import shutil
import time
import re
from bisect import bisect_right
from collections import defaultdict

TRACE_SECONDS = 4.0
HOST_PREFIX = "bench:"
ARRIVAL_WAIT = "engine:idle_wait"       # the engine's span around a wait for the next arrival
WAITING = "engine waiting for an arrival"


def serving_stretch(arrivals: list[float], burst: int, seconds: float,
                    trace_seconds: float = TRACE_SECONDS) -> tuple[float, float]:
    """(start, stop) of the traced stretch on the window's clock, from the
    schedule alone.  One arrival at a time: the last ``trace_seconds`` of
    arrivals, so that stopping the profiler stalls only the drain.  Bursts:
    from the due time of the last burst, whose work no program can have done
    before; a fast program has drained every earlier burst long before the
    arrivals end and would make no call there at all."""
    last = max(arrivals)
    if burst > 1:
        return last, last + trace_seconds
    return min(max(0.0, seconds - trace_seconds), last), seconds


class TraceWindow:
    def __init__(self, directory):
        self.dir = str(directory)
        self.start_at, self.stop_at = 0.0, float("inf")     # a serving window places them
        self.costs = {}
        self.active = False
        self.done = False
        self.armed = False
        self.interval = None        # (start, stop) on the window's clock

    def place(self, start_at: float, stop_at: float):
        self.start_at, self.stop_at = start_at, stop_at

    def arm(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        self.armed = True

    def tick(self, now: float):
        """Called at every call boundary of a serving window: the first call
        at or after ``start_at`` starts the profiler, the first at or after
        ``stop_at`` stops it (``finish`` does where the engine is done first)."""
        if self.done or not self.armed:
            return
        if not self.active and now >= self.start_at:
            self.start()
            self.interval = (now, None)
        elif self.active and now >= self.stop_at:
            self.interval = (self.interval[0], now)
            self.stop()

    def start(self):
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the benchmark's own spans are enough,
        options.host_tracer_level = 1       # and every Python call would swamp them
        options.enable_hlo_proto = False
        t = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.costs["start_s"] = time.perf_counter() - t
        self.active = True

    def stop(self):
        import jax
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.costs["stop_s"] = time.perf_counter() - t
        self.active, self.done = False, True

    def finish(self):
        if self.active:
            self.interval = ((self.interval or (0.0, None))[0], float("inf"))
            self.stop()
        self.armed = False

    def reduce(self) -> dict | None:
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None
        out = reduce_xplane(max(files, key=os.path.getmtime))
        shutil.rmtree(self.dir, ignore_errors=True)   # traces are large
        return out


def _union(intervals):
    """Total length of the union of (start, end) intervals, and the gaps."""
    busy, gaps, end = 0.0, [], None
    for a, b in sorted(intervals):
        if end is None:
            end = a
        if a > end:
            gaps.append((end, a))
            end = a
        if b > end:
            busy += b - end
            end = b
    return busy, gaps


def name_gaps(gaps, call_spans, wait_spans) -> dict:
    """Idle gaps ``(start, end)`` by what the host was doing, in the gaps'
    own unit.  The part of a gap that lies inside one of ``wait_spans`` (the
    engine waiting for the next arrival: sorted, not overlapping) is named
    so: a program that has nothing to do is not a lazy one.  The rest of the
    gap goes to the benchmark's call span ``(start, end, kind)`` that holds
    the gap's midpoint, or to the host between two calls."""
    call_starts = [c[0] for c in call_spans]
    wait_ends = [w[1] for w in wait_spans]
    named = defaultdict(float)
    for a, b in gaps:
        waited = 0
        for wa, wb in wait_spans[bisect_right(wait_ends, a):]:
            if wa >= b:
                break
            waited += min(b, wb) - max(a, wa)
        if waited > 0:
            named[WAITING] += waited
        mid = (a + b) / 2
        i = bisect_right(call_starts, mid) - 1
        inside = i >= 0 and mid < call_spans[i][1]
        named[f"in {call_spans[i][2]} call" if inside else "host between calls"] += b - a - waited
    return {k: v for k, v in named.items() if v > 0}


CONTAINERS = ("while", "conditional", "call")


def _parse(name: str) -> tuple[str, str, str]:
    """``%fusion.8 = bf16[4096,32768]{...} fusion(...)`` -> (``fusion.8``,
    ``fusion``, ``bf16[4096,32768]``): name, kind and first result shape."""
    head, sep, rest = name.partition(" = ")
    op = head.lstrip("%")
    if not sep:
        return op[:80], "", ""
    depth, i = 0, 0
    if rest.startswith("("):                  # a tuple of result shapes
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shapes, tail = rest[1:i], rest[i + 1:].lstrip()
    else:
        shapes, _, tail = rest.partition(" ")
    m = re.match(r"\w+\[[\d,]*\]", shapes)
    return op, tail.split("(")[0].strip(), m.group(0) if m else ""


def _short(name: str) -> str:
    op, kind, shape = _parse(name)
    return " ".join(x for x in (op, kind, shape) if x)


def reduce_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_spans, wait_spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host_spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                           ev.name[len(HOST_PREFIX):]))
                    elif ev.name == ARRIVAL_WAIT:
                        wait_spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    if not devices:
        return None
    host_spans.sort()
    wait_spans.sort()
    busy_all, window_all = [], []
    per_op = defaultdict(lambda: [0.0, 0])       # (module, op) -> [seconds, count]
    per_module = defaultdict(lambda: [0.0, 0])
    gap_by_host = defaultdict(float)
    for lines in devices:
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                      for ev in lines["XLA Modules"].events) if "XLA Modules" in lines else []
        mod_starts = [m[0] for m in mods]
        for a, b, name in mods:
            per_module[name][0] += (b - a) * 1e-9
            per_module[name][1] += 1
        intervals = []
        for ev in lines["XLA Ops"].events:
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            intervals.append((a, b))
            i = bisect_right(mod_starts, a) - 1
            module = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            if _parse(ev.name)[1] in CONTAINERS:   # its body's operations follow
                continue
            key = (module, _short(ev.name))
            per_op[key][0] += ev.duration_ns * 1e-9
            per_op[key][1] += 1
        if not intervals:
            continue
        busy_ns, gaps = _union(intervals)
        lo = min(a for a, _ in intervals)
        hi = max(b for _, b in intervals)
        busy_all.append(busy_ns * 1e-9)
        window_all.append((hi - lo) * 1e-9)
        if lines is devices[0]:
            for what, ns in name_gaps(gaps, host_spans, wait_spans).items():
                gap_by_host[what] += ns * 1e-9
    if not busy_all:
        return None
    ops = sorted(((f"{m}/{o}", s, n) for (m, o), (s, n) in per_op.items()),
                 key=lambda r: -r[1])
    return {"busy_s": sum(busy_all) / len(busy_all),
            "window_s": sum(window_all) / len(window_all),
            "chips_traced": len(busy_all),
            "arrival_wait_s": gap_by_host.get(WAITING, 0.0),
            "ops": [{"name": n, "seconds": s, "count": c} for n, s, c in ops],
            "modules": {k: {"seconds": v[0], "count": v[1]} for k, v in per_module.items()},
            "idle_gaps": sorted(gap_by_host.items(), key=lambda kv: -kv[1])}


def custom_calls(reduced: dict, top: int = 24) -> list:
    """The kernels in the trace: name, seconds, count."""
    rows = [o for o in reduced["ops"] if " custom-call" in o["name"]]
    return [[o["name"], o["seconds"], o["count"]] for o in rows[:top]]


def breakdown(reduced: dict) -> dict:
    chips = reduced["chips_traced"]       # seconds a chip, like busy_s
    return {"device_ops": [[o["name"], o["seconds"] / chips] for o in reduced["ops"][:10]],
            "idle_gaps": [[k, v] for k, v in reduced["idle_gaps"][:10]]}
