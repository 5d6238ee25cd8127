"""Decides ``correct`` for a serving cell.

After the window a sample of the finished requests (drawn from the seed,
the longest among them) is run once through the plain reference, prompt
and served tokens together, and every served token's reference logit is
compared with the reference's best at that position.  Greedy decoding
serves the best token, so the gap is rounding: bfloat16 weights and cache
against float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from ..reference import mistral as R

SAMPLE_TOKENS = 400
SAMPLE_MAX = 4


def pad_length(mix: dict) -> int:
    top = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    return -(-top // R.Q_BLOCK) * R.Q_BLOCK


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
                       "output": r["output"]})
        if (sum(len(p["output"]) for p in picked) >= SAMPLE_TOKENS
                or len(picked) >= SAMPLE_MAX):
            break
    return picked


def _programs(model: dict, quant):
    layer = jax.jit(partial(R.layer, model, quant=quant))

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def head(norm_w, head_w, x, rows):
        h = R.rms_norm(jnp.take(x[0], rows, axis=0), norm_w, model["rms_norm_eps"])
        return R._mm(h, head_w, quant)
    return embed, layer, head


def reference_logits(model, programs, weights, tokens, rows):
    """Logits (len(rows), vocab) at positions ``rows`` of one padded sequence."""
    embed, layer, head = programs
    x = embed(weights["model.embed_tokens.weight"], tokens[None])
    pos = jnp.arange(tokens.shape[0])
    for i in range(model["num_hidden_layers"]):
        x = layer(R.layer_weights(weights, i), x, pos)
    return head(weights["model.norm.weight"], weights["lm_head.weight"], x, rows)


def served_gaps(model: dict, seed: int, sample: list[dict], pad_to: int,
                control: str | None = None, out_rows: int | None = None) -> dict:
    """Widest and mean gap by which a served token's reference logit lies
    below the reference's best.  With ``control`` the token judged at each
    position is the one the lower precision puts first."""
    if not sample:
        return {"max": float("inf"), "mean": float("inf"), "tokens": 0,
                "ref_absmax": 0.0}
    weights = W.make_weights(model, seed)
    plain = _programs(model, None)
    lower = _programs(model, control) if control is not None else None
    # a fixed number of rows (the mix's longest answer): one program for every seed
    out_rows = out_rows or max(len(s["output"]) for s in sample)
    gaps, absmax = [], 0.0
    for s in sample:
        seq = np.zeros(pad_to, np.int32)
        both = list(s["prompt"]) + list(s["output"])
        seq[:len(both)] = both
        n, p = len(s["output"]), len(s["prompt"])
        rows = np.zeros(out_rows, np.int32)
        rows[:n] = np.arange(p - 1, p - 1 + n)      # position i predicts i+1
        tokens, rows_d = jnp.asarray(seq), jnp.asarray(rows)
        ref = reference_logits(model, plain, weights, tokens, rows_d)
        judged = jnp.asarray(np.pad(np.asarray(s["output"], np.int32), (0, out_rows - n)))
        if control is not None:
            judged = jnp.argmax(reference_logits(model, lower, weights, tokens,
                                                 rows_d), axis=-1)
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, judged[:, None], -1)[:, 0]
        gaps.append(np.asarray(gap)[:n])
        absmax = max(absmax, float(jnp.max(jnp.abs(ref[:n]))))
    allg = np.concatenate(gaps)
    return {"max": float(allg.max()), "mean": float(allg.mean()),
            "tokens": int(allg.size), "ref_absmax": absmax}
