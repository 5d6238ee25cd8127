"""Request-scoped tracing: named tracks, nested spans, async request
lifecycles, chrome://tracing JSON export.

Dapper-style (Sigelman et al., 2010) host-side tracing for the serving
stack: a ``Tracer`` collects timestamped events on named TRACKS (the
chrome-trace "thread" axis — the engine uses one track per decode slot
and one per tenant), and exports them as a chrome://tracing /
Perfetto-loadable JSON object. Timestamps come from a pluggable clock
so the serving engine's VIRTUAL clock (``EngineClock``) and wall time
(``time.perf_counter``) both work; durations are stored in clock
units (seconds for wall/measured clocks) and scaled to microseconds at
export, which is what the chrome trace format expects.

Event kinds map onto chrome trace phases:

- ``span`` / ``add_span``  -> complete events (ph "X"): nested work on
  one track (prefill, decode_n, a dense wave). Same-track spans must
  nest (contained or disjoint) — the engine emits them from a single
  sequential loop, so they do by construction.
- ``async_begin``/``async_end`` -> async events (ph "b"/"e"): REQUEST
  ROOT SPANS, which overlap freely on a tenant track (request B
  arrives before request A finishes).
- ``instant`` -> instant events (ph "i"): scheduler decisions (admit
  wave, shed, degrade), jit compiles.
- ``counter`` -> counter events (ph "C"): queue depth over time.

A process-global ACTIVE tracer (``use``/``activate``/``active``) lets
layers that cannot be threaded a tracer handle (the jit program cache,
``route_decode``) attach events to whatever trace is being recorded;
when none is active they fall through at the cost of one ``is None``
check. ``trace_id`` rides a contextvar: ``trace_scope(rid)`` tags
every span recorded inside with the owning request.

The profiler's span store (``paddle_tpu.profiler._spans``) is FED from
here too: while a ``profiler.Profiler`` is recording, every complete
span is mirrored into it, so ``Profiler.summary()`` tables include
obs spans without a second instrumentation pass.
"""
from __future__ import annotations

import contextvars
import json
import time
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional

_trace_id: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_obs_trace_id", default=None)


def get_trace_id() -> Optional[str]:
    """The request id owning the current context (None outside one)."""
    return _trace_id.get()


@contextmanager
def trace_scope(trace_id: str):
    """Tag every span/instant recorded inside with ``trace_id``."""
    tok = _trace_id.set(trace_id)
    try:
        yield
    finally:
        _trace_id.reset(tok)


class Tracer:
    """One trace: an event list plus a track-name -> tid registry.

    ``clock``: zero-arg callable returning the current time in this
    trace's units (default ``time.perf_counter``). The serving engine
    swaps in its virtual clock for the duration of a run.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock or time.perf_counter
        self._events: List[dict] = []
        self._tracks: Dict[str, int] = {}
        # the mirror seam: an optional per-event sink (the incident
        # flight recorder's bounded ring) fed alongside the event
        # list — one is-None check per recorded event, nothing when
        # tracing is off (no events are recorded at all then)
        self._sink: Optional[Callable[[dict], None]] = None

    # --- clock / tracks ---------------------------------------------------
    def set_clock(self, clock: Callable[[], float]):
        self._clock = clock

    def now(self) -> float:
        return self._clock()

    def track(self, name: str) -> int:
        """tid for a named track (assigned in first-use order, so track
        layout in the viewer follows instrumentation order)."""
        tid = self._tracks.get(name)
        if tid is None:
            tid = len(self._tracks) + 1
            self._tracks[name] = tid
        return tid

    def set_sink(self, sink: Optional[Callable[[dict], None]]):
        """Install (or clear, with None) the per-event mirror sink —
        ``obs.flight.FlightRecorder.attach`` uses this to keep a
        bounded ring of the most recent events."""
        self._sink = sink

    def _emit(self, evt: dict):
        self._events.append(evt)
        if self._sink is not None:
            self._sink(evt)

    # --- event emission ---------------------------------------------------
    def _args(self, attrs: dict) -> dict:
        tid = _trace_id.get()
        if tid is not None and "trace_id" not in attrs:
            attrs = dict(attrs, trace_id=tid)
        return attrs

    def add_span(self, name: str, t0: float, dur: float,
                 track: str = "main", **attrs):
        """A complete span with explicit start/duration (clock units)."""
        self._emit({"name": name, "ph": "X", "ts": t0,
                    "dur": max(dur, 0.0),
                    "tid": self.track(track),
                    "args": self._args(attrs)})

    @contextmanager
    def span(self, name: str, track: str = "main", **attrs):
        """Context-managed span on this tracer's clock."""
        t0 = self.now()
        try:
            yield self
        finally:
            self.add_span(name, t0, self.now() - t0, track=track, **attrs)

    def instant(self, name: str, t: Optional[float] = None,
                track: str = "main", **attrs):
        self._emit({"name": name, "ph": "i",
                    "ts": self.now() if t is None else t,
                    "s": "t", "tid": self.track(track),
                    "args": self._args(attrs)})

    def counter(self, name: str, value: float,
                t: Optional[float] = None, track: str = "counters"):
        self._emit({"name": name, "ph": "C",
                    "ts": self.now() if t is None else t,
                    "tid": self.track(track),
                    "args": {"value": value}})

    def async_begin(self, name: str, id_: str,
                    t: Optional[float] = None, track: str = "main",
                    cat: str = "request", **attrs):
        """Open an async (overlap-capable) span, e.g. a request root."""
        self._emit({"name": name, "ph": "b", "cat": cat,
                    "id": str(id_),
                    "ts": self.now() if t is None else t,
                    "tid": self.track(track),
                    "args": self._args(attrs)})

    def async_end(self, name: str, id_: str,
                  t: Optional[float] = None, track: str = "main",
                  cat: str = "request", **attrs):
        self._emit({"name": name, "ph": "e", "cat": cat,
                    "id": str(id_),
                    "ts": self.now() if t is None else t,
                    "tid": self.track(track),
                    "args": self._args(attrs)})

    # --- introspection / export -------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    @property
    def events(self) -> List[dict]:
        return list(self._events)

    def clear(self):
        """Empty the trace — events AND track registrations (a reused
        tracer must not export ghost tracks from a previous run; tids
        are re-derived on first use)."""
        self._events.clear()
        self._tracks.clear()

    def to_chrome(self, pid: int = 1,
                  process_name: str = "paddle_tpu") -> dict:
        """The chrome://tracing JSON object (ts/dur in microseconds)."""
        evts: List[dict] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name}}]
        for name, tid in sorted(self._tracks.items(),
                                key=lambda kv: kv[1]):
            evts.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": name}})
            evts.append({"name": "thread_sort_index", "ph": "M",
                         "pid": pid, "tid": tid,
                         "args": {"sort_index": tid}})
        for e in self._events:
            out = dict(e, pid=pid, ts=round(e["ts"] * 1e6, 3))
            if "dur" in out:
                out["dur"] = round(out["dur"] * 1e6, 3)
            evts.append(out)
        return {"traceEvents": evts,
                "displayTimeUnit": "ms"}

    def export(self, path: str, pid: int = 1,
               process_name: str = "paddle_tpu") -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(pid, process_name), f)
        return path


# --- host phases on the wall clock ---------------------------------------
class _OpenSpan:
    """A span while it is open; its own context manager. On exit it
    files one plain tuple with ``HostPhases`` (tuples of numbers and
    strings cost the collector nothing, objects that point at each
    other would)."""
    __slots__ = ("name", "rid", "id", "t0", "t1", "parent", "turn",
                 "child_s", "_hp", "_ann")

    def __init__(self, hp: "HostPhases", name: str, rid):
        self.name = name
        self.rid = rid
        self.child_s = 0.0
        self._hp = hp
        self._ann = None

    def __enter__(self):
        hp = self._hp
        self.parent = hp._open
        hp._open = self
        if self.name == "turn":
            hp.turns += 1
        self.turn = hp.turns
        self.id = hp._next_id
        hp._next_id += 1
        if hp._recording():     # a profiler session records
            self._ann = hp._annotation("engine:" + self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        hp = self._hp
        parent = hp._open = self.parent
        if parent is not None:
            parent.child_s += t1 - self.t0
        if hp.keep:
            hp.spans.append((self.name, self.t0, t1, self.id,
                             None if parent is None else parent.id,
                             self.turn, self.rid, self.child_s))
        return False


class HostPhases:
    """The host spans of one engine run, in memory until the run ends.

    ``span(name, rid)`` opens a child of whatever span is open; a span
    named ``turn`` starts the next turn. A closed span is the tuple
    ``(name, t0, t1, id, parent id, turn, rid, child_s)``: times on
    ``time.perf_counter``, ``parent id`` None for a root, ``child_s``
    the seconds its children cover (self time is ``t1 - t0 -
    child_s``). ``call(...)`` files one device call's four stamps so
    that ``summary`` can split it into seam, dispatch and wait.
    ``keep=False`` (fixed-clock runs, whose length is unbounded and
    whose ``overhead`` is None) times and annotates spans and stores
    none."""

    FIELDS = ("name", "t0", "t1", "id", "parent", "turn", "rid",
              "child_s")

    def __init__(self, keep: bool = True):
        from jax.profiler import TraceAnnotation  # obs imports no jax
        self._annotation = TraceAnnotation
        self._recording = TraceAnnotation.is_enabled
        self.keep = keep
        self.spans: List[tuple] = []    # closed spans, children first
        self.calls: List[tuple] = []    # (kind, rows, w0, t_in, t_enq, w1)
        self.turns = 0
        self._next_id = 0
        self._open: Optional[_OpenSpan] = None

    def span(self, name: str, rid=None) -> _OpenSpan:
        return _OpenSpan(self, name, rid)

    def call(self, kind: str, rows: int, call: _OpenSpan,
             dispatch: _OpenSpan):
        """File a closed ``call.<kind>`` span and its ``dispatch``
        child: the clock's own code ran from ``call.t0`` to
        ``dispatch.t0`` (the seam), the wrapped function to
        ``dispatch.t1`` (uploads and enqueue), the wait for the
        result to ``call.t1``."""
        if self.keep:
            self.calls.append((kind, rows, call.t0, dispatch.t0,
                               dispatch.t1, call.t1))

    def records(self) -> List[dict]:
        """The closed spans as dicts keyed by ``FIELDS``."""
        return [dict(zip(self.FIELDS, s)) for s in self.spans]

    def summary(self, t_zero: float) -> dict:
        """The run's accounting: ``phases`` (self seconds by name, the
        ``turn`` roots and the calls left out), ``calls`` (per kind,
        one entry a call: start since ``t_zero``, then the seam's, the
        dispatch's and the wait's seconds), ``idle_wait_s``,
        ``unaccounted_s`` (the turns' self time) and ``root_s`` (the
        seconds under root spans). They conserve: phases + calls +
        unaccounted == root_s."""
        phases: Dict[str, dict] = {}
        unaccounted = root_s = 0.0
        for name, t0, t1, _, parent, _, _, child_s in self.spans:
            self_s = t1 - t0 - child_s
            if parent is None:
                root_s += t1 - t0
            if name == "turn":
                unaccounted += self_s
            elif not name.startswith(("call.", "dispatch.")):
                row = phases.setdefault(
                    name, {"n": 0, "self_s": 0.0, "max_s": 0.0})
                row["n"] += 1
                row["self_s"] += self_s
                row["max_s"] = max(row["max_s"], self_s)
        calls: Dict[str, dict] = {}
        for kind, rows, w0, t_in, t_enq, w1 in self.calls:
            row = calls.setdefault(
                kind, {"n": 0, "rows": 0, "start_s": [], "seam_s": [],
                       "dispatch_s": [], "wait_s": []})
            row["n"] += 1
            row["rows"] += rows
            row["start_s"].append(w0 - t_zero)
            row["seam_s"].append(t_in - w0)
            row["dispatch_s"].append(t_enq - t_in)
            row["wait_s"].append(w1 - t_enq)
        idle = phases.get("idle_wait")
        return {"turns": self.turns, "phases": phases, "calls": calls,
                "idle_wait_s": 0.0 if idle is None else idle["self_s"],
                "unaccounted_s": unaccounted, "root_s": root_s}

    def to_tracer(self, tracer: "Tracer", t_zero: float):
        """The spans as complete events on the ``engine.host`` track
        of a tracer whose clock is wall seconds since ``t_zero``."""
        names = {s[3]: s[0] for s in self.spans}
        for name, t0, t1, _, parent, turn, rid, _ in sorted(
                self.spans, key=lambda s: s[1]):
            attrs = {"turn": turn}
            if parent is not None:
                attrs["parent"] = names[parent]
            if rid is not None:
                attrs["rid"] = rid
            tracer.add_span(name, t0 - t_zero, t1 - t0,
                            track="engine.host", **attrs)


# --- the process-global active tracer -----------------------------------
_active: Optional[Tracer] = None


def active() -> Optional[Tracer]:
    """The tracer currently recording, or None (the common, free case)."""
    return _active


def activate(tracer: Tracer):
    global _active
    _active = tracer


def deactivate():
    global _active
    _active = None


@contextmanager
def use(tracer: Optional[Tracer]):
    """Install ``tracer`` as the process-global active tracer for the
    duration (None is allowed and is a no-op, so call sites need no
    branch)."""
    global _active
    prev = _active
    if tracer is not None:
        _active = tracer
    try:
        yield tracer
    finally:
        _active = prev
