"""The family of Moonlight-16B-A3B (``model_type: deepseek_v3``): a
pre-norm decoder with latent (MLA) attention without query compression, a
dense SwiGLU in its first ``first_k_dense_replace`` layers and then a
sparse feed-forward of ``n_routed_experts`` experts (``num_experts_per_tok``
a token, sigmoid scores, a per-expert selection bias, normalised and
scaled weights) beside ``n_shared_experts`` shared ones, an untied head.
The program runs it as a ``DeepseekV3ForCausalLM`` through
``ServingEngine`` (a latent paged cache, absorbed attention).

The one place of the benchmark that knows this model: which keys of a
configuration file describe it, its leaves in the program's ``state_dict``
names, how the program is built for serving, what a pass costs (the
published operations: attention counted expanded, whatever path the program
takes), and how the plain reference
(``benchmark/reference/deepseek_v3.py``) judges what was served.

**Serving only.**  The family's training entries (``training_program``,
``param_shardings``, ``train_step``, ``train_step_flops``) are absent: the
model's training path (the loss with ``seq_aux``, the grouped products'
backward, a chip's share of the experts) is no part of the program yet,
and the harness asks for them only in a training cell.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import latent_moe_flops as F
from benchmark.harness.spec import reference_module

R = reference_module(__file__, "deepseek_v3")

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "moe_layer_freq", "kv_lora_rank", "q_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "norm_topk_prob",
              "routed_scaling_factor", "scoring_func", "topk_method", "n_group",
              "topk_group", "rms_norm_eps", "rope_theta", "tie_word_embeddings",
              "attention_bias", "hidden_act")


def sparse_layer(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


# -- leaves ---------------------------------------------------------------
def leaf_shapes(cfg: dict) -> dict:
    """name -> shape, in the names the program's state_dict uses (linear
    weights (in, out); a layer's experts stacked over their number; the
    shared experts one SwiGLU of their summed width)."""
    H, V, nh = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    E, I = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    S = cfg["n_shared_experts"] * I
    shapes = {"model.embed_tokens.weight": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes[p + "input_layernorm.weight"] = (H,)
        shapes[p + "self_attn.q_proj.weight"] = (H, nh * (nope + rope))
        shapes[p + "self_attn.kv_a_proj_with_mqa.weight"] = (H, rank + rope)
        shapes[p + "self_attn.kv_a_layernorm.weight"] = (rank,)
        shapes[p + "self_attn.kv_b_proj.weight"] = (rank, nh * (nope + dv))
        shapes[p + "self_attn.o_proj.weight"] = (nh * dv, H)
        shapes[p + "post_attention_layernorm.weight"] = (H,)
        if sparse_layer(cfg, i):
            shapes[p + "mlp.gate.weight"] = (H, E)
            shapes[p + "mlp.gate.e_score_correction_bias"] = (E,)
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
    """The norms' gains: the leaves of rank one but the router's selection
    bias, which is drawn about zero like a matrix."""
    return len(shape) == 1 and not name.endswith("e_score_correction_bias")


# -- the program ----------------------------------------------------------
def program_config(model: dict, max_positions: int):
    from paddle_tpu.models.nlp.deepseek_v3 import DeepseekV3Config
    return DeepseekV3Config(**{k: model[k] for k in MODEL_KEYS},
                            max_position_embeddings=max_positions, dtype=jnp.bfloat16)


def serving_program(model: dict, engine: dict):
    """What ``ServingEngine`` takes as its model: shapes only, no weight
    made (``load_weights`` brings them)."""
    from paddle_tpu.models.nlp.deepseek_v3 import DeepseekV3ForCausalLM
    net = DeepseekV3ForCausalLM(program_config(model, engine["max_len"]))
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
    """Weights one position is multiplied by in all layers: the attention
    projections (``W_q``, ``W_kva``, ``W_kvb`` — absorbed, its two halves
    ``W_UK`` on the query and ``W_UV`` on the output: the same count —
    ``W_o``), the dense layers' SwiGLU,
    and in a sparse layer the router, ``num_experts_per_tok`` experts and
    the shared expert."""
    H, nh, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    attn = (H * nh * (nope + rope) + H * (rank + rope) + nh * nope * rank
            + nh * rank * dv + nh * dv * H)
    I = cfg["moe_intermediate_size"]
    sparse = (H * cfg["n_routed_experts"]
              + (cfg["num_experts_per_tok"] + cfg["n_shared_experts"]) * 3 * H * I)
    dense = 3 * H * cfg["intermediate_size"]
    n_sparse = sum(sparse_layer(cfg, i) for i in range(cfg["num_hidden_layers"]))
    n_dense = cfg["num_hidden_layers"] - n_sparse
    return cfg["num_hidden_layers"] * attn + n_sparse * sparse + n_dense * dense


def forward_flops(cfg: dict, new_tokens: int, context_start: int,
                  head_tokens: int | None = None) -> float:
    """Forward pass of ``new_tokens`` tokens that follow ``context_start``
    cached ones; the head runs on ``head_tokens`` of them (all by default)."""
    head_tokens = new_tokens if head_tokens is None else head_tokens
    body = 2.0 * token_matmul_params(cfg) * new_tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    # token i (0-based) attends to context_start + i + 1 keys
    pairs = new_tokens * context_start + new_tokens * (new_tokens + 1) / 2.0
    return body + head + F.expanded_pair_flops(cfg) * cfg["num_hidden_layers"] * pairs


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
    layer = jax.jit(partial(R.layer, model, quant=quant))

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
        x = layer(R.layer_weights(weights, i), x, pos)
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
