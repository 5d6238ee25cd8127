"""Latency readers of a serving window: all requests, wall clock."""
from __future__ import annotations

import math

import numpy as np

from benchmark.harness.sentinel import spans_a_stop


def _percentile(values, q):
    values = [v for v in values if v is not None]
    if not values:
        return None
    if any(math.isinf(v) for v in values):
        finite = sorted(values)
        k = int(math.ceil(q / 100.0 * len(finite))) - 1
        return finite[max(k, 0)]
    return float(np.percentile(np.asarray(values), q))


def _ttfts(obs):
    """Seconds from the instant a request was due to its first token on the
    host; a request that never got one misses every limit."""
    return [r["token_times"][0] - r["arrival"] if r["token_times"] else math.inf
            for r in obs["requests"]]


def _gaps(obs):
    """Gaps between consecutive output tokens of every request, less those
    that span a stop of the whole machine (``obs["machine_stops"]``, seen by
    the sentinel process).  The program's own pauses all stay in."""
    stops = obs.get("machine_stops") or ()
    out = []
    for r in obs["requests"]:
        t = r["token_times"]
        out.extend(b - a for a, b in zip(t, t[1:]) if not spans_a_stop(a, b, stops))
    return out


def gaps_left_out(obs) -> int:
    """How many gaps ``_gaps`` leaves out for spanning a stop of the machine."""
    return len(_gaps(dict(obs, machine_stops=()))) - len(_gaps(obs))


def ttft_percentile(obs, params):
    if obs["kind"] != "serve":
        return None
    return 1e3 * _percentile(_ttfts(obs), params["q"])


def gap_percentile(obs, params):
    gaps = _gaps(obs) if obs["kind"] == "serve" else []
    return 1e3 * _percentile(gaps, params["q"]) if gaps else None


def gap_tail_mean(obs, params):
    """Mean of the largest ``share`` of all gaps between consecutive output
    tokens of all requests."""
    gaps = sorted(_gaps(obs)) if obs["kind"] == "serve" else []
    if not gaps:
        return None
    k = max(1, int(round(params["share"] * len(gaps))))
    return 1e3 * float(np.mean(gaps[-k:]))


def queue_wait_percentile(obs, params):
    if obs["kind"] != "serve":
        return None
    waits = [r["admit"] - r["arrival"] if r["admit"] is not None else math.inf
             for r in obs["requests"]]
    return 1e3 * _percentile(waits, params["q"])


def output_tokens_per_s(obs, params):
    """All output tokens of the window's requests over the whole window,
    first arrival to last token."""
    if obs["kind"] != "serve" or obs["window_s"] <= 0:
        return None
    return sum(len(r["token_times"]) for r in obs["requests"]) / obs["window_s"]
