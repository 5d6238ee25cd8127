"""Readers of the reduced device trace (harness/trace.py)."""
from __future__ import annotations

import re

from benchmark import flops

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _trace(obs):
    return obs.get("device_trace")


def idle_share(obs, params):
    """Share of the traced stretch in which no operation ran, the engine's
    waits for the next arrival taken out of the idle seconds and of the
    stretch alike: having nothing to do is not idling."""
    t = _trace(obs)
    if not t:
        return None
    stretch = t["window_s"] - t.get("arrival_wait_s", 0.0)
    if stretch <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / stretch)


def _op_seconds(t, module, kind=None, shape=None, prefixes=None):
    """Seconds (summed over chips) of the operations of ``module`` whose kind,
    first result shape or name match."""
    total = 0.0
    for op in t["ops"]:
        mod, _, rest = op["name"].partition("/")
        parts = rest.split(" ")
        if module is not None and mod != module:
            continue
        if kind is not None and (len(parts) < 2 or parts[1] != kind):
            continue
        if shape is not None and not (len(parts) > 2 and re.search(shape, parts[2])):
            continue
        if prefixes is not None and not any(
                p.startswith(prefixes) for p in parts[:2]):
            continue
        total += op["seconds"]
    return total


def collective_exposed_share(obs, params):
    """Share of the traced window in which a collective held the core's
    operation line, so that no compute ran beside it."""
    t = _trace(obs)
    if not t or obs["chips"] < 2:
        return None
    secs = _op_seconds(t, params["module"], prefixes=COLLECTIVES)
    return 100.0 * secs / (t["window_s"] * t["chips_traced"])


def flash_roofline(obs, params):
    """The flash kernels' share of their roofline: the attention operations
    of the traced steps over the bf16 peak, against the kernels' seconds."""
    t = _trace(obs)
    if not t or obs["kind"] != "train" or obs.get("peak") is None:
        return None
    job, model = obs["job"], obs["model"]
    shape = rf"\[[\d,]*{job['seq']},{model['head_dim']}\]"      # (.., seq, head_dim)
    secs = _op_seconds(t, params["module"], kind="custom-call", shape=shape)
    if secs <= 0:
        return None
    per_step = model["num_hidden_layers"] * (
        flops.flash_flops(job["batch"], job["seq"], model["num_attention_heads"],
                          model["head_dim"], backward=False)
        + flops.flash_flops(job["batch"], job["seq"], model["num_attention_heads"],
                            model["head_dim"], backward=True))
    least = obs["steps"] * per_step / obs["peak"]["bf16_flops_per_s"]
    return 100.0 * least / secs


def paged_attn_roofline(obs, params):
    """The paged decode kernel's share of its roofline over the traced
    stretch: the keys and values its rows had to read, at the chip's memory
    bandwidth (or its operations at the peak, whichever is longer)."""
    t = _trace(obs)
    span = obs.get("trace_interval")
    if not t or obs["kind"] != "serve" or not span or obs.get("peak") is None:
        return None
    secs = _op_seconds(t, params["module"], kind="custom-call")
    if secs <= 0:
        return None
    a, b = span
    context = sum(r["prompt_len"] + i
                  for r in obs["requests"]
                  for i, stamp in enumerate(r["token_times"])
                  if i > 0 and a < stamp <= b)
    if context <= 0:
        return None
    least = flops.roofline_seconds(flops.paged_decode_flops(obs["model"], context),
                                   flops.paged_decode_bytes(obs["model"], context),
                                   obs["peak"])
    return 100.0 * least / secs
