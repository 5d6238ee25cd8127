"""Readers of what a latent-attention expert model adds: the counts its
serving factory keeps of every device call (the engine hands them on as
``ServeResult.overhead["model_counts"]``, so ``obs["overhead"]``: one entry
a program call of the window, in call order — ``kind`` (``prefill`` a
chunk, ``decode`` a ``decode_n``), ``layer_calls``, ``pairs``,
``experts_hit``, ``max_expert_pairs`` (each summed over the call's steps and
layers) and ``cached_tokens_read``), the operations of the expert products
and of the latent attention kernel in the reduced device trace, and the
prefix cache's hits in the requests' own rows.  A program that keeps no
such counts (any other model, or the parent of the PR that added them)
gives nothing to read, and every reader then returns None.
"""
from __future__ import annotations

from benchmark import flops, latent_moe_flops

PRODUCTS = "ragged-dot"          # XLA:TPU's grouped-matmul custom call


def _calls(obs):
    """The counted calls with the end of each on the window's clock: the
    benchmark's spans hold every call into the factories in the same order
    (a ``prefill`` span of ``units`` chunks is ``units`` chunk programs)."""
    counts = (obs.get("overhead") or {}).get("model_counts")
    if obs.get("kind") != "serve" or not counts or not counts.get("kind"):
        return None
    ends = []
    for kind, _, end, units in obs.get("spans", []):
        if kind in ("prefill", "decode"):
            ends += [(kind, end)] * (int(units) if kind == "prefill" and units else 1)
    if [k for k, _ in ends] != list(counts["kind"]):
        return None              # the two records are not of the same calls
    names = [k for k in counts if k != "kind"]
    return [dict({n: counts[n][i] for n in names}, kind=kind, end=end)
            for i, (kind, end) in enumerate(ends)]


def _traced(obs):
    """The counted calls that ended inside the traced stretch."""
    calls, span = _calls(obs), obs.get("trace_interval")
    if calls is None or not span or not obs.get("device_trace"):
        return None
    a, b = span
    return [c for c in calls if a < c["end"] <= b]


def _seconds(t, match):
    """Seconds (summed over chips) of the trace's operations that ``match``
    accepts, given (module, operation name, kind)."""
    total = 0.0
    for op in t["ops"]:
        module, _, rest = op["name"].partition("/")
        name, kind = (rest.split(" ") + ["", ""])[:2]
        if match(module, name, kind):
            total += op["seconds"]
    return total


def _is_product(module, name, kind):
    return kind == "custom-call" and name.startswith(PRODUCTS)


def load_max_over_mean(obs, params):
    """The largest expert's pairs over the mean expert's, over every
    expert-layer call of the window (1 = perfectly even routing)."""
    calls, model = _calls(obs), obs.get("model") or {}
    pairs = sum(c["pairs"] for c in calls) if calls else 0
    if not pairs or "n_routed_experts" not in model:
        return None
    return model["n_routed_experts"] * sum(c["max_expert_pairs"] for c in calls) / pairs


def moe_share(obs, params):
    """The expert products' device seconds over the traced busy seconds."""
    t = obs.get("device_trace")
    if not t or _calls(obs) is None or t["busy_s"] <= 0:
        return None
    return 100.0 * _seconds(t, _is_product) / (t["busy_s"] * t["chips_traced"])


def expert_roofline(obs, params):
    """The expert products' share of their roofline over the traced
    stretch: the matrices of the experts that received a token, read once
    at the chip's memory bandwidth (or the pairs' operations at the peak,
    whichever is longer, call by call), against the products' device
    seconds."""
    inside, t = _traced(obs), obs.get("device_trace")
    if not inside or obs.get("peak") is None:
        return None
    secs = _seconds(t, _is_product)
    if secs <= 0:
        return None
    model = obs["model"]
    least = sum(flops.roofline_seconds(
        latent_moe_flops.expert_products_flops(model, c["pairs"]),
        latent_moe_flops.expert_products_bytes(model, c["experts_hit"]), obs["peak"])
        for c in inside)
    return 100.0 * least / secs


def latent_attn_roofline(obs, params):
    """The latent attention kernel's share of its roofline in the traced
    decode calls: the latent cache positions their rows attended to (the
    program's own count), read once a layer at the chip's memory bandwidth
    (or their operations at the peak, if longer), against the seconds of
    the kernel's calls in the decode program (``params["module"]``; the
    kernel is found by the name its ``pallas_call`` states)."""
    inside, t = _traced(obs), obs.get("device_trace")
    if not inside or obs.get("peak") is None:
        return None
    secs = _seconds(t, lambda module, name, kind: (
        module == params["module"] and kind == "custom-call"
        and name.startswith(params["kernel"])))
    read = sum(c["cached_tokens_read"] for c in inside if c["kind"] == "decode")
    if secs <= 0 or read <= 0:
        return None
    model = obs["model"]
    least = flops.roofline_seconds(latent_moe_flops.latent_decode_flops(model, read),
                                   latent_moe_flops.latent_decode_bytes(model, read),
                                   obs["peak"])
    return 100.0 * least / secs


def latent_attn_share(obs, params):
    """The latent attention kernel's device seconds in every program
    (decode steps and prefill chunks alike) over the traced busy seconds."""
    t = obs.get("device_trace")
    if not t or _calls(obs) is None or t["busy_s"] <= 0:
        return None
    secs = _seconds(t, lambda module, name, kind: (
        kind == "custom-call" and name.startswith(params["kernel"])))
    return 100.0 * secs / (t["busy_s"] * t["chips_traced"])


def prefix_hit_share(obs, params):
    """Prompt tokens served from retained pages over prompt tokens."""
    rows = obs.get("requests") or []
    asked = sum(r["prompt_len"] for r in rows if r["token_times"])
    if obs.get("kind") != "serve" or asked <= 0:
        return None
    return 100.0 * sum(r["cached"] for r in rows if r["token_times"]) / asked
