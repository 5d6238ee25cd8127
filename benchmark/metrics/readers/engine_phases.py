"""Readers of the engine's own wall-clock accounting
(``ServeResult.overhead``, which the harness hands on as
``obs["overhead"]``): the self time of its host phases and the
seam/dispatch/wait split of every call into the decode factories, counted
where the work happens.  Shares are percent of ``obs["window_s"]``.  A
program that does not count them (the parent of the PR that added the
counts) gives nothing to read, and every reader then returns None."""
from __future__ import annotations

import numpy as np


def _accounting(obs, key):
    ov = obs.get("overhead")
    if obs.get("kind") != "serve" or not ov or key not in ov or obs["window_s"] <= 0:
        return None
    return ov


def phase_share(obs, params):
    """Self seconds of the phases named in ``params["phases"]``; without
    that key, of every host phase and the turns' own unnamed time: calls,
    their seams and the waits for an arrival left out."""
    ov = _accounting(obs, "phases")
    if ov is None:
        return None
    names = params.get("phases")
    if names is None:
        host = ov["unaccounted_s"] + sum(
            row["self_s"] for name, row in ov["phases"].items() if name != "idle_wait")
    else:
        host = sum(ov["phases"][n]["self_s"] for n in names if n in ov["phases"])
    return 100.0 * host / obs["window_s"]


def occupancy(obs, params):
    """Rows that rode the calls of one kind over the rows they had room
    for: how full the fixed-shape batch ran."""
    ov = _accounting(obs, "calls")
    calls = ov["calls"].get(params["kind"]) if ov else None
    if not calls or not calls["n"] or not ov.get("slots"):
        return None
    return 100.0 * calls["rows"] / (calls["n"] * ov["slots"])


def call_share(obs, params):
    """One part of every call (``seam_s``, ``dispatch_s`` or ``wait_s``),
    summed over all kinds.  With ``params["excess"]`` only what each call
    spent beyond the median of its kind: the stalls."""
    ov = _accounting(obs, "calls")
    if ov is None or not ov["calls"]:
        return None
    total = 0.0
    for row in ov["calls"].values():
        part = np.asarray(row[params["part"]], dtype=float)
        if params.get("excess"):
            part = np.maximum(part - np.median(part), 0.0)
        total += float(part.sum())
    return 100.0 * total / obs["window_s"]
