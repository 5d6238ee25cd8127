"""Readers of what a linear-attention model with a per-sequence state adds:
the counts its serving factory keeps of every device call beside the expert
layer's (``latent_tokens_read``, ``kda_rows_stepped``,
``kda_chunk_positions``: the engine hands them on in
``ServeResult.overhead["model_counts"]``), the engine's census of what the
rows held by kind and of its snapshots (``overhead["kv_pages_held"]``,
``["kv_page_bytes"]``, ``["prefix_tokens_cut_by_snapshot"]``,
``["prefix_tokens_matched"]``), and the KDA decode kernel's and the latent
kernel's seconds in the reduced device trace.  A program that keeps no such
counts (any other model, or the parent of the PR that added them) gives
nothing to read, and every reader then returns None.  The counted calls,
the traced ones among them and the trace's seconds are read as
``readers/latent_moe.py`` reads them.
"""
from __future__ import annotations

import re
from pathlib import Path

from benchmark import flops, linear_latent_flops
from benchmark.harness.spec import load_module

_L = load_module(Path(__file__).with_name("latent_moe.py"))


def _kernel_seconds(t, kernel, module=None):
    return _L._seconds(t, lambda mod, name, kind: (
        (module is None or mod == module) and kind == "custom-call"
        and name.startswith(kernel)))


def _decode_calls(obs):
    """The traced decode calls, where the program counts KDA rows."""
    inside = _L._traced(obs)
    if not inside or obs.get("peak") is None or "kda_rows_stepped" not in inside[0]:
        return None
    return [c for c in inside if c["kind"] == "decode"]


def kda_state_roofline(obs, params):
    """The KDA decode kernel's share of its roofline in the traced decode
    calls: the states its rows stepped (the program's own count: rows x KDA
    layers, summed over steps), each read once and written once at the
    chip's memory bandwidth (or their operations at the peak, if longer),
    against the seconds of the kernel's calls in the decode program
    (``params["module"]``; the kernel by the name its ``pallas_call``
    states)."""
    dec = _decode_calls(obs)
    if not dec:
        return None
    secs = _kernel_seconds(obs["device_trace"], params["kernel"], params["module"])
    rows = sum(c["kda_rows_stepped"] for c in dec)
    if secs <= 0 or rows <= 0:
        return None
    model = obs["model"]
    least = flops.roofline_seconds(linear_latent_flops.kda_step_flops(model, rows),
                                   linear_latent_flops.kda_step_bytes(model, rows),
                                   obs["peak"])
    return 100.0 * least / secs


def kda_share(obs, params):
    """The KDA layers' own device seconds over the traced busy seconds: the
    decode kernel by its name, in every module, and in the lane's program
    (``params["chunk_module"]``) the operations of the KDA layers, which the
    reduced trace shows by their kind and the shape of their first result
    alone (``params["chunk_ops"]``, a pattern over ``"<kind> <shape>"`` that
    no other layer of the model makes at the cell's widths: float32 results
    over heads, chunk and head size — the decay, the states, a chunk's
    triangular system — the projections to heads x head size and to the
    gates' rank, the convolutions' inputs)."""
    t = obs.get("device_trace")
    calls = _L._calls(obs)
    if not t or not calls or "kda_rows_stepped" not in calls[0] or t["busy_s"] <= 0:
        return None
    mine = re.compile(params["chunk_ops"])
    secs = _kernel_seconds(t, params["kernel"])
    for op in t["ops"]:
        module, _, rest = op["name"].partition("/")
        parts = rest.split(" ")
        if module == params["chunk_module"] and len(parts) > 2 \
                and mine.fullmatch(parts[1] + " " + parts[2]):
            secs += op["seconds"]
    if secs <= 0:
        return None
    return 100.0 * secs / (t["busy_s"] * t["chips_traced"])


def latent_attn_roofline(obs, params):
    """``latent_moe:latent_attn_roofline`` for a model whose layers are not
    all latent: the program's count is already summed over its latent
    layers."""
    dec = _decode_calls(obs)
    if not dec:
        return None
    secs = _kernel_seconds(obs["device_trace"], params["kernel"], params["module"])
    read = sum(c["latent_tokens_read"] for c in dec)
    if secs <= 0 or read <= 0:
        return None
    model = obs["model"]
    least = flops.roofline_seconds(linear_latent_flops.latent_decode_flops(model, read),
                                   linear_latent_flops.latent_decode_bytes(model, read),
                                   obs["peak"])
    return 100.0 * least / secs


def cache_held_vs_all_latent(obs, params):
    """Bytes of cache the running rows held (latent pages and state
    entries), summed over the turns sampled, over what the same rows' pages
    would have held were every layer a latent one (percent; lower is
    better: what the state costs against the pages it replaces)."""
    ov = obs.get("overhead") or {}
    held, pb = ov.get("kv_pages_held"), ov.get("kv_page_bytes")
    if not held or not pb or "state" not in held or not held["latent"]:
        return None
    own = held["latent"] * pb["latent"] + held["state"] * pb["state"]
    return 100.0 * own / (held["latent"] * pb["latent_all_layers"])


def prefix_cut_by_snapshot_share(obs, params):
    """Matched prompt tokens that were recomputed because no snapshot stood
    at the matched chain's end, over the matched tokens."""
    ov = obs.get("overhead") or {}
    matched = ov.get("prefix_tokens_matched")
    if not matched:
        return None
    return 100.0 * ov["prefix_tokens_cut_by_snapshot"] / matched
