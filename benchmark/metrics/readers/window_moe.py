"""Readers of what a window-and-global expert model adds: the two counts
its serving factory keeps of every device call beside the expert layer's
(``kv_tokens_read_global``, ``kv_tokens_read_window``: the engine hands
them on in ``ServeResult.overhead["model_counts"]``), the engine's census
of its two kinds of page (``overhead["kv_pages_held"]``,
``["kv_pages_if_all_global"]``, ``["kv_page_bytes"]``), and the paged
kernel's seconds in the reduced device trace.  A program that keeps no such
counts (any other model, or the parent of the PR that added them) gives
nothing to read, and every reader then returns None.  The counted calls,
the traced ones among them and the trace's seconds are read as
``readers/latent_moe.py`` reads them.
"""
from __future__ import annotations

from pathlib import Path

from benchmark import flops, window_moe_flops
from benchmark.harness.spec import load_module

_L = load_module(Path(__file__).with_name("latent_moe.py"))


def load_max_over_mean(obs, params):
    """The largest expert's pairs over the mean expert's, over every
    expert-layer call of the window (1 = perfectly even routing)."""
    calls, model = _L._calls(obs), obs.get("model") or {}
    pairs = sum(c["pairs"] for c in calls) if calls else 0
    if not pairs or "num_experts" not in model:
        return None
    return model["num_experts"] * sum(c["max_expert_pairs"] for c in calls) / pairs


def _kernel_seconds(t, params, module=None):
    return _L._seconds(t, lambda mod, name, kind: (
        (module is None or mod == module) and kind == "custom-call"
        and name.startswith(params["kernel"])))


def paged_attn_roofline(obs, params):
    """The paged kernel's share of its roofline in the traced decode calls:
    the K and V behind the positions their rows' walks read (the program's
    own counts, by kind: a sliding layer's row at most its window), read
    once at the chip's memory bandwidth (or their operations at the peak,
    if longer), against the seconds of the kernel's calls in the decode
    program (``params["module"]``; the kernel by the name its
    ``pallas_call`` states)."""
    inside, t = _L._traced(obs), obs.get("device_trace")
    if not inside or obs.get("peak") is None \
            or "kv_tokens_read_global" not in inside[0]:
        return None
    secs = _kernel_seconds(t, params, params["module"])
    dec = [c for c in inside if c["kind"] == "decode"]
    g = sum(c["kv_tokens_read_global"] for c in dec)
    w = sum(c["kv_tokens_read_window"] for c in dec)
    if secs <= 0 or g + w <= 0:
        return None
    model = obs["model"]
    least = flops.roofline_seconds(window_moe_flops.kv_read_flops(model, g, w),
                                   window_moe_flops.kv_read_bytes(model, g, w),
                                   obs["peak"])
    return 100.0 * least / secs


def attn_share(obs, params):
    """The paged kernel's device seconds in every program (decode steps and
    prefill chunks alike) over the traced busy seconds."""
    t = obs.get("device_trace")
    calls = _L._calls(obs)
    if not t or not calls or "kv_tokens_read_global" not in calls[0] \
            or t["busy_s"] <= 0:
        return None
    secs = _kernel_seconds(t, params)
    if secs <= 0:
        return None
    return 100.0 * secs / (t["busy_s"] * t["chips_traced"])


def kv_held_vs_all_global(obs, params):
    """Bytes of cache the running rows held, summed over the turns sampled,
    over what the same rows would have held were every layer global
    (percent; lower is better: the window kind's saving)."""
    ov = obs.get("overhead") or {}
    held, pb = ov.get("kv_pages_held"), ov.get("kv_page_bytes")
    if not held or not pb or not ov.get("kv_pages_if_all_global"):
        return None
    every = ov["kv_pages_if_all_global"] * (pb["global"] + pb["window"])
    return 100.0 * window_moe_flops.held_bytes(held["global"], held["window"], pb) / every
