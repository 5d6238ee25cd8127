"""The one general traffic generator: a mix is a data file of parameters.

Every seed gets the same multiset of arrival gaps, prompt lengths, output
lengths and prefix memberships (drawn from the mix's ``shape_seed`` and the
window length); ``--seed`` draws the token ids and the order: ``"order":
"shuffle"`` (the default) deals the sizes and gaps out anew, ``"rotate"``
enters one fixed schedule at another point, ``"fixed"`` keeps the schedule
and draws only the token ids.  So two seeds offer the same work.
"""
from __future__ import annotations

import numpy as np


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def serve_requests(mix: dict, seconds: float, seed: int, vocab: int) -> list[dict]:
    """Open-loop requests due in [0, seconds): dicts with ``rid``, ``arrival``
    (seconds from the window's start), ``prompt`` (token ids),
    ``max_new_tokens`` and ``prefix_group``."""
    burst = int(mix.get("burst", 1))
    n_events = max(1, int(round(mix["rate_per_s"] * seconds / burst)))
    n = n_events * burst
    shape = np.random.default_rng([int(mix["shape_seed"]), int(round(seconds * 1000))])
    gaps = shape.exponential(1.0, n_events + 1)
    prompt_len = _lengths(shape, mix["prompt"], n)
    out_len = _lengths(shape, mix["output"], n)
    sp = mix.get("shared_prefix") or {"share": 0.0, "groups": 0, "tokens": 0}
    in_group = np.zeros(n, bool)
    in_group[:int(round(sp["share"] * n))] = True

    order = np.random.default_rng([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 7])
    in_group = shape.permutation(in_group)      # sessions spread through the schedule
    group = np.where(in_group, np.cumsum(in_group) % max(1, sp["groups"]), -1)
    how = mix.get("order", "shuffle")
    if how == "fixed":
        # one schedule for every seed: a tail below the knee is a property of
        # which requests meet, and any reordering moves it (PERF.md, section 2)
        perm = np.arange(n)
    elif how == "rotate":
        # the same schedule for every seed, entered at another point: which
        # requests meet stays as it is, so a tail reads the same schedule
        k = int(order.integers(0, n_events))
        gaps = np.concatenate([np.roll(gaps[:-1], -k), gaps[-1:]])
        perm = np.roll(np.arange(n), -k * burst)
    else:
        gaps = np.concatenate([order.permutation(gaps[:-1]), gaps[-1:]])
        perm = order.permutation(n)
    prompt_len, out_len, group = prompt_len[perm], out_len[perm], group[perm]
    times = np.cumsum(gaps)[:n_events] * (seconds / gaps.sum())
    arrival = np.repeat(times, burst)
    ids = np.random.default_rng([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 11])
    prefixes = ids.integers(0, vocab, (max(1, sp["groups"]), sp["tokens"]))
    reqs = []
    for i in range(n):
        g = int(group[i])
        if g >= 0:      # a session opens with its system prompt
            own = max(int(prompt_len[i]) - sp["tokens"], int(mix["prompt"]["min"]) // 2)
            prompt = np.concatenate([prefixes[g], ids.integers(0, vocab, own)])
            prompt = prompt[:int(mix["prompt"]["max"])]
        else:
            prompt = ids.integers(0, vocab, int(prompt_len[i]))
        reqs.append({"rid": f"r{i:05d}", "arrival": float(arrival[i]),
                     "prompt": tuple(int(t) for t in prompt),
                     "max_new_tokens": int(out_len[i]),
                     "prefix_group": g if g >= 0 else None})
    return reqs


def warmup_requests(mix: dict, vocab: int, chunk: int) -> list[dict]:
    """A few requests that touch every shape the mix can: the longest
    prompt (whose chunks cover every shorter padded length), a shared
    prefix met twice (the resume path), and enough short ones to fill the
    decode batch."""
    rng = np.random.default_rng(12345)
    top = int(mix["prompt"]["max"])
    sp = mix.get("shared_prefix") or {"tokens": 0}
    lens = [top, top - chunk // 2]
    reqs = []
    prefix = rng.integers(0, vocab, sp["tokens"])
    for j in range(2 if sp["tokens"] else 0):
        own = rng.integers(0, vocab, chunk + 5 * j)
        reqs.append((np.concatenate([prefix, own]), 0))
    for L in lens + [int(mix["prompt"]["min"])] * 6:
        reqs.append((rng.integers(0, vocab, L), None))
    return [{"rid": f"w{i:03d}", "arrival": 0.05 * i,
             "prompt": tuple(int(t) for t in p), "max_new_tokens": 4 + i,
             "prefix_group": g} for i, (p, g) in enumerate(reqs)]


def summary(reqs: list[dict]) -> dict:
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new_tokens"] for r in reqs])
    return {"requests": len(reqs), "prompt_mean": float(p.mean()),
            "prompt_max": int(p.max()), "output_mean": float(o.mean()),
            "output_max": int(o.max()),
            "in_prefix_groups": int(sum(r["prefix_group"] is not None for r in reqs)),
            "last_due_s": float(max(r["arrival"] for r in reqs))}
