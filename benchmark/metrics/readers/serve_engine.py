"""Readers of the benchmark's spans around the calls into the decode
factories, and of the engine's share of the window."""
from __future__ import annotations

import numpy as np


def _spans(obs, kind):
    return [s for s in obs.get("spans", []) if s[0] == kind]


def span_median_ms(obs, params):
    """Median length of the calls of one kind; ``per_unit`` divides a call
    by the chunks it computed."""
    spans = _spans(obs, params["kind"])
    if not spans:
        return None
    if params.get("per_unit"):
        vals = [(b - a) / u for _, a, b, u in spans if u]
    else:
        vals = [b - a for _, a, b, _ in spans]
    return 1e3 * float(np.median(vals)) if vals else None


def host_share(obs, params):
    """Share of the window in which the engine was neither inside a device
    call nor waiting for an arrival: scheduling, bookkeeping, sampling."""
    if obs["kind"] != "serve" or obs["window_s"] <= 0:
        return None
    host = obs["window_s"] - obs["engine_dev_wall_s"] - obs["slept_s"]
    return 100.0 * max(host, 0.0) / obs["window_s"]


def prefill_tokens_per_s(obs, params):
    """Prompt tokens computed over the time inside prefill calls."""
    spent = sum(b - a for _, a, b, _ in _spans(obs, "prefill"))
    if obs["kind"] != "serve" or spent <= 0:
        return None
    return obs["prefill_tokens"] / spent
