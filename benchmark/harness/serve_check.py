"""Decides ``correct`` for a serving cell.

After the window a sample of the finished requests (drawn from the seed,
the longest among them) is run once through the plain reference, and every
served token's reference logit is compared with the reference's best where
that token is judged.  Greedy decoding serves the best token, so the gap is
rounding: bfloat16 weights and cache against float32.  Which reference
logits judge which served token is the family's to say
(``family.served_logits``); the sample, the gaps, the control and the
limits are the harness's.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import weights as W

SAMPLE_TOKENS = 400
SAMPLE_MAX = 4


def pick_sample(rows: list[dict], reqs: list[dict], seed: int, mix: dict) -> list[dict]:
    """The longest finished request, one served from the prefix cache when
    there is one, then others drawn from the seed, up to some hundreds of
    served tokens."""
    prompts = {r["rid"]: r["prompt"] for r in reqs}
    done = [r for r in rows if r["done"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 13])
    order = [done[i] for i in rng.permutation(len(done))]
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["output"]))
    cached = next((r for r in order if r["cached"] > 0), None)
    picked = []
    for r in [longest, cached] + order:
        if r is None or any(p["rid"] == r["rid"] for p in picked):
            continue
        picked.append({"rid": r["rid"], "prompt": prompts[r["rid"]],
                       "output": r["output"], "record": r})
        if (sum(len(p["output"]) for p in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_MAX):
            break
    return picked


def served_gaps(family, model: dict, seed: int, sample: list[dict], pad_to: int,
                control: str | None = None, out_rows: int | None = None) -> dict:
    """Widest and mean gap by which a served token's reference logit lies
    below the reference's best.  With ``control`` the token judged at each
    position is the one the lower precision puts first."""
    if not sample:
        return {"max": float("inf"), "mean": float("inf"), "tokens": 0,
                "ref_absmax": 0.0}
    weights = W.make_weights(family, model, seed)
    plain = family.reference_programs(model, None)
    lower = family.reference_programs(model, control) if control is not None else None
    # a fixed number of rows (the mix's longest answer): one program for every seed
    out_rows = out_rows or max(len(s["output"]) for s in sample)
    gaps, absmax = [], 0.0
    for s in sample:
        n = len(s["output"])
        ref = family.served_logits(model, plain, weights, s, pad_to, out_rows)
        judged = jnp.asarray(np.pad(np.asarray(s["output"], np.int32), (0, out_rows - n)))
        if control is not None:
            judged = jnp.argmax(family.served_logits(model, lower, weights, s, pad_to,
                                                     out_rows), axis=-1)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, judged[:, None], -1)[:, 0]
        gaps.append(np.asarray(gap)[:n])
        absmax = max(absmax, float(jnp.max(jnp.abs(ref[:n]))))
    allg = np.concatenate(gaps)
    return {"max": float(allg.max()), "mean": float(allg.mean()),
            "tokens": int(allg.size), "ref_absmax": absmax}
