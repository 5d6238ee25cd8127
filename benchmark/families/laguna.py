"""The family of Laguna-XS.2 (``model_type: laguna``): a pre-norm decoder
whose layers attend in two ways — ``full_attention`` (causal, half of each
head rotated under YaRN) and ``sliding_attention`` (the last
``sliding_window`` positions, the whole head rotated plainly), with query
head counts of their own over shared KV heads — a per-head sigmoid gate on
the attention output, a dense SwiGLU where ``mlp_layer_types`` says so and
otherwise a sparse feed-forward of ``num_experts`` experts
(``num_experts_per_tok`` a token, sigmoid scores normalised and scaled, no
selection bias) beside one shared expert, an untied head.  The program runs
it as a ``LagunaForCausalLM`` through ``ServingEngine`` (two kinds of paged
K/V pool: global layers' pages grow with a sequence, window layers' are
given back behind the window).

The one place of the benchmark that knows this model: which keys of a
configuration file describe it, its leaves in the program's ``state_dict``
names, how the program is built for serving, what a pass costs (a sliding
layer's pairs counted to the window), and how the plain reference
(``benchmark/reference/laguna.py``) judges what was served.  The three
per-layer lists of a configuration file are the published ones, whole: a
cut in depth reads their first ``num_hidden_layers`` entries.

**Serving only**, as ``deepseek_v3.py``: the training entries are absent
(the expert layer's backward is no part of the program yet), and the
harness asks for them only in a training cell.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import window_moe_flops as F
from benchmark.harness.spec import reference_module

R = reference_module(__file__, "laguna")

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim", "attention_bias",
              "rms_norm_eps", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "tie_word_embeddings", "gating",
              "sliding_window", "rope_parameters", "layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer", "moe_apply_router_weight_on_input",
              "partial_rotary_factor", "moe_routed_scaling_factor")


def sparse_layer(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


# -- leaves ---------------------------------------------------------------
def leaf_shapes(cfg: dict) -> dict:
    """name -> shape, in the names the program's state_dict uses (linear
    weights (in, out); a layer's experts stacked over their number)."""
    H, V, D = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    nkv = cfg["num_key_value_heads"]
    E, I = cfg["num_experts"], cfg["moe_intermediate_size"]
    S = cfg["shared_expert_intermediate_size"]
    shapes = {"model.embed_tokens.weight": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        n = cfg["num_attention_heads_per_layer"][i]
        shapes[p + "input_layernorm.weight"] = (H,)
        shapes[p + "self_attn.q_proj.weight"] = (H, n * D)
        shapes[p + "self_attn.k_proj.weight"] = (H, nkv * D)
        shapes[p + "self_attn.v_proj.weight"] = (H, nkv * D)
        shapes[p + "self_attn.gate_proj.weight"] = (H, n)
        shapes[p + "self_attn.o_proj.weight"] = (n * D, H)
        shapes[p + "post_attention_layernorm.weight"] = (H,)
        if sparse_layer(cfg, i):
            shapes[p + "mlp.gate.weight"] = (H, E)
            shapes[p + "mlp.experts.gate_proj"] = (E, H, I)
            shapes[p + "mlp.experts.up_proj"] = (E, H, I)
            shapes[p + "mlp.experts.down_proj"] = (E, I, H)
            shapes[p + "mlp.shared_experts.gate_proj.weight"] = (H, S)
            shapes[p + "mlp.shared_experts.up_proj.weight"] = (H, S)
            shapes[p + "mlp.shared_experts.down_proj.weight"] = (S, H)
        else:
            shapes[p + "mlp.gate_proj.weight"] = (H, cfg["intermediate_size"])
            shapes[p + "mlp.up_proj.weight"] = (H, cfg["intermediate_size"])
            shapes[p + "mlp.down_proj.weight"] = (cfg["intermediate_size"], H)
    shapes["model.norm.weight"] = (H,)
    shapes["lm_head.weight"] = (H, V)
    return shapes


def is_gain(name: str, shape) -> bool:
    """The norms' gains: every leaf of rank one."""
    return len(shape) == 1


# -- the program ----------------------------------------------------------
def program_config(model: dict, max_positions: int):
    from paddle_tpu.models.nlp.laguna import LagunaConfig
    return LagunaConfig(**{k: model[k] for k in MODEL_KEYS},
                        max_position_embeddings=max_positions, dtype=jnp.bfloat16)


def serving_program(model: dict, engine: dict):
    """What ``ServingEngine`` takes as its model: shapes only, no weight
    made (``load_weights`` brings them)."""
    from paddle_tpu.models.nlp.laguna import LagunaForCausalLM
    net = LagunaForCausalLM(program_config(model, engine["max_len"]))
    net.eval()
    return net


def load_weights(net, weights: dict):
    """The model keeps the drawn arrays themselves; the harness's dict
    gives its references up."""
    net.load_tree(weights)
    weights.clear()


def drop_weights(net):
    net.drop_weights()


# -- operations -----------------------------------------------------------
def token_matmul_params(cfg: dict) -> int:
    """Weights one position is multiplied by in all layers: a layer's four
    projections and its gate at its own head count, the dense layers'
    SwiGLU, and in a sparse layer the router, ``num_experts_per_tok``
    experts and the shared expert."""
    H, D, nkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    I, S = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    total = 0
    for i in range(cfg["num_hidden_layers"]):
        n = cfg["num_attention_heads_per_layer"][i]
        total += 2 * H * n * D + 2 * H * nkv * D + H * n
        if sparse_layer(cfg, i):
            total += H * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * H * I + 3 * H * S
        else:
            total += 3 * H * cfg["intermediate_size"]
    return total


def forward_flops(cfg: dict, new_tokens: int, context_start: int,
                  head_tokens: int | None = None) -> float:
    """Forward pass of ``new_tokens`` tokens that follow ``context_start``
    cached ones; the head runs on ``head_tokens`` of them (all by default).
    A sliding layer's token attends to at most ``sliding_window`` keys."""
    head_tokens = new_tokens if head_tokens is None else head_tokens
    body = 2.0 * token_matmul_params(cfg) * new_tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    return body + head + F.attention_flops(cfg, new_tokens, context_start)


def request_flops(cfg: dict, row: dict) -> tuple:
    """The passes one served request cost, from its own record: the prompt
    less what the prefix cache held, the head on its last token alone; then
    one pass of one token for every output token after the first."""
    new = row["prompt_len"] - row["cached"]
    n_dec = len(row["token_times"]) - 1
    return (forward_flops(cfg, new, row["cached"], head_tokens=1),
            forward_flops(cfg, n_dec, row["prompt_len"]))


# -- the reference's judgement of what was served -------------------------
def pad_length(mix: dict) -> int:
    top = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    return -(-top // R.Q_BLOCK) * R.Q_BLOCK


def reference_programs(model: dict, quant):
    """One compiled layer a distinct shape of layer (its kind, dense or
    sparse), the embedding and the head.  ``quant``: None, the control
    ``"int8"``, or the planted fault ``"window_ignored"``."""
    layers = {}
    for i in range(model["num_hidden_layers"]):
        shape = (model["layer_types"][i], model["mlp_layer_types"][i])
        if shape not in layers:
            layers[shape] = jax.jit(partial(R.layer, model, i, quant=quant))

    def layer(i, w, x, pos):
        return layers[(model["layer_types"][i], model["mlp_layer_types"][i])](w, x, pos)

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def head(norm_w, head_w, x, rows):
        h = R.rms_norm(jnp.take(x, rows, axis=0), norm_w, model["rms_norm_eps"])
        return R._mm(h, head_w, quant)
    return embed, layer, head


def reference_logits(model, programs, weights, tokens, rows):
    """Logits (len(rows), vocab) at positions ``rows`` of one padded sequence."""
    embed, layer, head = programs
    x = embed(weights["model.embed_tokens.weight"], tokens)
    pos = jnp.arange(tokens.shape[0])
    for i in range(model["num_hidden_layers"]):
        x = layer(i, R.layer_weights(weights, i), x, pos)
    return head(weights["model.norm.weight"], weights["lm_head.weight"], x, rows)


def served_logits(model, programs, weights, served: dict, pad_to: int, out_rows: int):
    """The reference's logits at which each served token of one sampled
    request is judged, ``(out_rows, vocab)``, the first ``len(output)`` rows
    in use: one causal pass over prompt and served tokens together, where
    position i predicts i+1, so row ``p - 1 + k`` judges output ``k``."""
    seq = np.zeros(pad_to, np.int32)
    both = list(served["prompt"]) + list(served["output"])
    seq[:len(both)] = both
    n, p = len(served["output"]), len(served["prompt"])
    rows = np.zeros(out_rows, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    return reference_logits(model, programs, weights, jnp.asarray(seq), jnp.asarray(rows))
