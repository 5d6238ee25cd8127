"""A second family for the tests, added to a copy of the benchmark as new
files only (``conftest.py::tiny_root`` copies this file to
``families/toy.py`` and ``toy_reference.py`` to ``reference/toy.py``).

Not Mistral-shaped where the harness used to be: it reads a key of its own
(``final_gain``), has a leaf the dense layers lack (``out_gain``, one gain a
channel on the final hidden state, before the head), counts that gain's
operations, and is served two tokens at one stamp (``decode_chunk`` 2 in its
``engine``), which its count of passes reads from the request's own stamps.
Its reference holds ``heads x head_dim != hidden``; the only serving program
the tree has derives ``head_dim``, so cells that run the program keep them
equal and the wide shape goes through the reference alone.

The program: for training a ``LlamaForCausalLM`` whose forward applies the
gain; for serving, where the decode factories read the dense leaves by name,
a plain one with the gain folded into the final norm's.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from benchmark.harness.spec import load_module, reference_module

M = load_module(Path(__file__).with_name("mistral.py"))      # shapes and passes of the dense part
R = reference_module(__file__, "toy")

MODEL_KEYS = M.MODEL_KEYS + ("final_gain",)


def leaf_shapes(cfg: dict) -> dict:
    shapes = M.leaf_shapes(cfg)
    if cfg["final_gain"]:
        shapes["out_gain"] = (cfg["hidden_size"],)
    return shapes


is_gain = M.is_gain
drop_weights = M.drop_weights
param_shardings = M.param_shardings
train_step = M.train_step
pad_length = M.pad_length


def serving_program(model: dict, engine: dict):
    return M.empty_model(model, engine["max_len"])


def training_program(model: dict, job: dict):
    from paddle_tpu.models.nlp import LlamaForCausalLM
    from paddle_tpu.nn import initializer as init

    class GainedLM(LlamaForCausalLM):
        def __init__(self, config):
            super().__init__(config)
            self.out_gain = self.create_parameter(
                [config.hidden_size], default_initializer=init.Constant(1.0))

        def forward(self, input_ids, positions=None):
            return self.lm_head(self.model(input_ids, positions) * self.out_gain)

    net = GainedLM(M.llama_config(model, job["seq"]))
    drop_weights(net)
    net.eval()
    net.to(dtype="bfloat16")
    return net


def load_weights(net, weights: dict):
    if "out_gain" not in net.state_dict():      # serving: fold the gain into the final norm's
        weights = dict(weights)
        gain = weights.pop("out_gain").astype(jnp.float32)
        folded = weights["model.norm.weight"].astype(jnp.float32) * gain
        weights["model.norm.weight"] = folded.astype(jnp.bfloat16)
    net.load_tree(weights)


def forward_flops(cfg, new_tokens, context_start, head_tokens=None):
    head_tokens = new_tokens if head_tokens is None else head_tokens
    return (M.forward_flops(cfg, new_tokens, context_start, head_tokens)
            + cfg["hidden_size"] * head_tokens)


def train_step_flops(cfg, batch, seq):
    return 3.0 * batch * forward_flops(cfg, seq, 0)


def request_flops(cfg, row) -> tuple:
    """Two tokens a stamp: the passes after the first are counted stamp by
    stamp from the request's own record, each of as many tokens as share it."""
    stamps = row["token_times"]
    new = row["prompt_len"] - row["cached"]
    parts = [forward_flops(cfg, new, row["cached"], head_tokens=1)]
    at = row["prompt_len"]
    for stamp in sorted(set(stamps[1:])):
        n = stamps[1:].count(stamp)
        parts.append(forward_flops(cfg, n, at))
        at += n
    return tuple(parts)


def reference_programs(model: dict, quant):
    embed, layer, _ = M.reference_programs(model, quant)

    @jax.jit
    def head(norm_w, gain, head_w, x, rows):
        h = R.final_hidden(jnp.take(x[0], rows, axis=0), norm_w, gain, model["rms_norm_eps"])
        return R._mm(h, head_w, quant)
    return embed, layer, head


def served_logits(model, programs, weights, served: dict, pad_to: int, out_rows: int):
    """One causal pass; row ``p - 1 + k`` judges output ``k`` (the program is
    autoregressive), through this family's gained head.  The record the
    harness hands on says how the tokens came: after the first, two a stamp."""
    record = served.get("record")
    if record is not None:
        stamps = record["token_times"]
        if len(set(stamps[1:])) > len(stamps) // 2:
            raise ValueError(f"not two tokens a stamp: {stamps}")
    embed, layer, head = programs
    gained = lambda norm_w, head_w, x, rows: head(norm_w, weights["out_gain"], head_w, x, rows)
    return M.served_logits(model, (embed, layer, gained), weights, served, pad_to, out_rows)
