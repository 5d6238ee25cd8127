#!/usr/bin/env python3
"""What the device's idle gaps of a serving cell are made of, by engine span.

    python3 tools/engine_gaps.py --workload serve_chat_steady --seed 7

Runs one ``--trace 1`` run of a benchmark cell as ``benchmark/run.py`` does,
but reads the profiler's ``.xplane.pb`` before the harness deletes it, and
splits every gap between operations on the first device by what the host
was doing in it, from the ``engine:`` and ``factory:`` annotations that
``ServingEngine._phase`` / ``_timed`` and ``prefill_chunked`` write:

- ``between calls: <phase>``: outside every ``engine:call.*``, by the
  innermost engine span (``turn`` is the loop's own time);
- ``seam.<kind>``: inside a call, before the wrapped function runs (the
  clock's own code: the benchmark's profiler start and stop fall here);
- ``dispatch.<kind>[/<factory step>]``: inside the wrapped function,
  before it returns: argument uploads and enqueue;
- ``launch.<kind>``: function returned, first operation not yet started;
- ``between ops.<kind>``: between two operations of the call;
- ``completion.<kind>``: last operation done, call not yet returned.

Also: each call of the traced stretch that ran 100 ms over its kind's
median, with the part that was long; and, from the engine's own accounting
of the whole window (``ServeResult.overhead``), the calls that ran longest
over their kind's median, split into seam, dispatch and wait.  The result
goes to ``chiprun_out/engine_gaps/<workload>.<seed>.trace<0|1>.json``;
``--trace 0`` runs without the profiler and reports the accounting alone.

This is a scratch copy of the benchmark's reduction: the follow-up
``benchmark`` issue in ROADMAP.md moves the attribution into
``benchmark/harness/trace.py``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
from bisect import bisect_left, bisect_right
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

PREFIXES = ("engine:", "factory:")
LONG_CALL_S = 0.1


def load_xplane(path: str):
    """(host spans, operations of the first device), in seconds: spans as
    ``(start, end, name)`` of the ``engine:``/``factory:`` annotations,
    operations as ``(start, end)`` sorted by start."""
    from jax.profiler import ProfileData
    spans, ops = [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9, ev.name))
        elif plane.name.startswith("/device:TPU:") and ops is None:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = sorted((ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
                                 for ev in line.events)
    return spans, ops or []


def _segments(spans):
    """The host's time cut at every span boundary: ``(start, end, stack)``
    with the names of the spans open there, outermost first."""
    marks = []
    for a, b, name in spans:
        marks.append((a, 1, -b, name))      # open: outer (longer) first
        marks.append((b, 0, -a, name))      # close before an open at the same instant
    marks.sort()
    out, stack, last = [], [], None
    for t, opens, _, name in marks:
        if last is not None and t > last and stack:
            out.append((last, t, tuple(stack)))
        if opens:
            stack.append(name)
        else:
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] == name:
                    del stack[i]
                    break
        last = t
    return out


def _label(stack, t, call_ops):
    """What the host was doing at ``t`` given the spans open there."""
    call = next((n for n in stack if n.startswith("engine:call.")), None)
    if call is None:
        inner = next((n for n in reversed(stack) if n.startswith("engine:")), None)
        return "between calls: " + (inner[len("engine:"):] if inner else "outside every turn")
    kind = call[len("engine:call."):]
    if any(n.startswith("engine:dispatch.") for n in stack):
        step = next((n for n in reversed(stack) if n.startswith("factory:")), None)
        return f"dispatch.{kind}" + (f"/{step[len('factory:'):]}" if step else "")
    dispatch_end, first_op, last_op = call_ops
    if dispatch_end is None or t < dispatch_end:
        return f"seam.{kind}"
    if first_op is None or t < first_op:
        return f"launch.{kind}"
    if t >= last_op:
        return f"completion.{kind}"
    return f"between ops.{kind}"


def attribute_gaps(spans, ops):
    """Seconds of device idle by label, over the stretch from the first
    operation to the last; plus the stretch and its busy seconds."""
    if not ops:
        return None
    from benchmark.harness.trace import _union    # the reducer's own rule
    busy, gaps = _union(ops)
    op_starts = [a for a, _ in ops]
    op_ends = sorted(b for _, b in ops)
    calls = sorted((a, b, n) for a, b, n in spans if n.startswith("engine:call."))
    call_starts = [c[0] for c in calls]
    dispatches = sorted((a, b) for a, b, n in spans if n.startswith("engine:dispatch."))
    dispatch_starts = [d[0] for d in dispatches]

    def ops_of_call(t):
        """(dispatch end, first operation's start, last operation's end)
        of the call open at ``t``."""
        i = bisect_right(call_starts, t) - 1
        a, b, _ = calls[i]
        j = bisect_left(dispatch_starts, a)
        if j >= len(dispatches) or dispatches[j][1] > b:
            return None, None, None
        d_end = dispatches[j][1]
        lo = bisect_left(op_starts, d_end)
        if lo >= len(ops) or op_starts[lo] >= b:
            return d_end, None, None
        hi = bisect_right(op_ends, b)
        return d_end, op_starts[lo], op_ends[hi - 1] if hi else None

    segs = _segments(spans)
    seg_starts = [s[0] for s in segs]
    by_label = defaultdict(float)
    for a, b in gaps:
        i = max(bisect_right(seg_starts, a) - 1, 0)
        t = a
        while t < b:
            if i < len(segs) and segs[i][1] <= t:
                i += 1
                continue
            if i >= len(segs) or segs[i][0] >= b:
                by_label["between calls: outside every turn"] += b - t
                break
            s0, s1, stack = segs[i]
            if s0 > t:
                by_label["between calls: outside every turn"] += s0 - t
                t = s0
            e = min(s1, b)
            in_call = any(n.startswith("engine:call.") for n in stack)
            if in_call and not any(n.startswith("engine:dispatch.") for n in stack):
                # the call's own time: cut it again at the first and the
                # last operation of the call
                call_ops = ops_of_call(t)
                cuts = sorted(c for c in call_ops if c is not None and t < c < e)
                for c in cuts + [e]:
                    by_label[_label(stack, (t + c) / 2, call_ops)] += c - t
                    t = c
            else:
                by_label[_label(stack, t, None)] += e - t
                t = e
    stretch = ops[-1][1] - ops[0][0]
    return {"stretch_s": stretch, "busy_s": busy, "idle_s": stretch - busy,
            "idle_by_host": dict(sorted(by_label.items(), key=lambda kv: -kv[1]))}


def long_calls(spans, ops):
    """Calls of the traced stretch over their kind's median by 100 ms."""
    by_kind = defaultdict(list)
    for a, b, n in spans:
        if n.startswith("engine:call."):
            by_kind[n[len("engine:call."):]].append((a, b))
    dispatches = sorted((a, b) for a, b, n in spans if n.startswith("engine:dispatch."))
    out = []
    for kind, calls in by_kind.items():
        median = sorted(b - a for a, b in calls)[len(calls) // 2]
        for a, b in calls:
            if b - a < median + LONG_CALL_S:
                continue
            d = next(((x, y) for x, y in dispatches if a <= x and y <= b), None)
            inside = [(max(x, a), min(y, b)) for x, y in ops if y > a and x < b]
            busy = sum(y - x for x, y in inside)
            out.append({"kind": kind, "call_s": b - a, "median_s": median,
                        "seam_s": None if d is None else d[0] - a,
                        "dispatch_s": None if d is None else d[1] - d[0],
                        "wait_s": None if d is None else b - d[1],
                        "device_busy_s": busy})
    return out


def window_stalls(overhead: dict, top: int = 8):
    """From the engine's accounting of the whole window: per kind the
    medians of seam, dispatch and wait, and the calls longest over their
    kind's median with the part that was long."""
    if not overhead or "calls" not in overhead:
        return None
    out = {"medians_ms": {}, "calls_over_median": []}
    rows = []
    for kind, c in overhead["calls"].items():
        med = {p: sorted(c[p])[len(c[p]) // 2] for p in ("seam_s", "dispatch_s", "wait_s")}
        out["medians_ms"][kind] = {p: 1e3 * v for p, v in med.items()}
        whole = med["seam_s"] + med["dispatch_s"] + med["wait_s"]
        for i in range(c["n"]):
            over = c["seam_s"][i] + c["dispatch_s"][i] + c["wait_s"][i] - whole
            rows.append((over, kind, i, c))
    for over, kind, i, c in sorted(rows, key=lambda r: -r[0])[:top]:
        out["calls_over_median"].append(
            {"kind": kind, "at_s": c["start_s"][i], "over_median_ms": 1e3 * over,
             "seam_ms": 1e3 * c["seam_s"][i], "dispatch_ms": 1e3 * c["dispatch_s"][i],
             "wait_ms": 1e3 * c["wait_s"][i]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: no profiler, the engine's own accounting alone")
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "engine_gaps"))
    ap.add_argument("--keep-xplane", action="store_true",
                    help="copy the .xplane.pb beside the result (tens of MB)")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    from benchmark.harness import serve as bench_serve
    from benchmark.harness import trace as bench_trace
    from benchmark.harness.spec import Spec

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    stem = f"{args.workload}.{args.seed}.trace{args.trace}"
    found = {}
    reduce_as_is = bench_trace.TraceWindow.reduce
    observe_as_is = bench_serve.observe

    def reduce_and_read(self):
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if files:
            newest = max(files, key=os.path.getmtime)
            spans, ops = load_xplane(newest)
            found["gaps"] = attribute_gaps(spans, ops)
            found["long_calls_in_trace"] = long_calls(spans, ops)
            found["spans_in_trace"] = len(spans)
            if args.keep_xplane:
                shutil.copy(newest, out_dir / f"{stem}.xplane.pb")
        return reduce_as_is(self)

    def observe_and_keep(*a, **kw):
        found["obs"] = observe_as_is(*a, **kw)
        return found["obs"]

    bench_trace.TraceWindow.reduce = reduce_and_read
    bench_serve.observe = observe_and_keep
    spec = Spec()
    seconds = args.seconds or spec.bench["run_seconds"]
    result = bench_run.run_cell(spec, args.workload, args.seed, seconds,
                                bool(args.trace))
    obs = found.get("obs", {})
    overhead = obs.get("overhead") or {}
    report = {"workload": args.workload, "seed": args.seed, "seconds": seconds,
              "result": result,
              # a traced run's line has no end-to-end metrics: read them too,
              # for the cost of tracing against the same seed untraced
              "end_to_end": spec.read_metrics(spec.end_to_end(args.workload), obs),
              "gaps": found.get("gaps"),
              "long_calls_in_trace": found.get("long_calls_in_trace"),
              "spans_in_trace": found.get("spans_in_trace"),
              "window_s": obs.get("window_s"),
              "overhead": overhead,
              "window_stalls": window_stalls(overhead)}
    (out_dir / f"{stem}.json").write_text(json.dumps(report, indent=1) + "\n")
    print("engine_gaps " + json.dumps({k: report[k] for k in
                                       ("gaps", "long_calls_in_trace", "window_stalls")}))
    # how often the lane's fused call engaged (absent before it existed)
    print("lane " + json.dumps({k: overhead.get(k) for k in
                                ("lane_calls", "lane_chunks", "lane_calls_by_width")}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
