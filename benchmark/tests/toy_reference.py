"""Plain reference of the tests' toy family: Mistral's decoder
(``reference/mistral.py``, beside this file once both are in a root) with
one gain a channel on the final hidden state, ``out_gain``, before the head.
Imports nothing of the program."""
from __future__ import annotations

from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmark.harness.spec import load_module

D = load_module(Path(__file__).with_name("mistral.py"))
_mm, adamw, leaf_norm, leaf_diff_norm = D._mm, D.adamw, D.leaf_norm, D.leaf_diff_norm


def final_hidden(x, norm_w, gain, eps):
    return D.rms_norm(x, norm_w, eps) * gain.astype(D.F32)


def logits_of(cfg, weights, tokens, quant=None, remat=False):
    h = D.hidden_states(cfg, weights, tokens, quant, remat) * weights["out_gain"].astype(D.F32)
    return D.logits(weights, h, quant)


def loss_fn(cfg, weights, tokens, labels, quant=None, by_row=True):
    """Mean next-token cross-entropy over (B, S), rows one at a time."""
    @jax.checkpoint
    def rows(t, l):
        lg = logits_of(cfg, weights, t, quant, remat=True)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(lg, l[..., None], -1)[..., 0], axis=-1)
    if by_row:
        per_row = jax.lax.map(lambda a: rows(a[0][None], a[1][None])[0], (tokens, labels))
    else:
        per_row = rows(tokens, labels)
    return jnp.mean(per_row)


def loss_and_grads(cfg, quant, by_row, params, tokens, labels):
    return jax.value_and_grad(partial(loss_fn, cfg, quant=quant, by_row=by_row))(
        params, tokens, labels)
