"""The family of Kimi-Linear-48B-A3B-Instruct (``model_type:
kimi_linear``): a pre-norm decoder whose layers mix in two ways — KDA
(gated delta-rule linear attention with a per-channel decay, a short
convolution and a float32 state a sequence) where
``linear_attn_config.kda_layers`` says so and latent (MLA) attention
without rotary where ``full_attn_layers`` does — a dense SwiGLU in its
first ``first_k_dense_replace`` layers and then a sparse feed-forward
(``num_experts_per_token`` of the router's ``router_width`` experts a
token, sigmoid scores, a selection bias, normalised and scaled weights)
beside one shared expert, an untied head.  The program runs it as a
``KimiLinearForCausalLM`` through ``ServingEngine`` (a latent paged pool
beside one state entry a sequence, prefix hits resumed from state
snapshots).

**A chip's share of the experts.**  ``num_experts`` counts the experts
HELD here (``experts_held`` names them, in the stacks' order);
``router_width`` is the published count the router scores.  Program and
reference alike compute what the held experts add and leave the rest out.

The one place of the benchmark that knows this model: which keys of a
configuration file describe it, its leaves in the program's ``state_dict``
names, how the program is built for serving, what a pass costs (KDA by its
published operations a token, MLA expanded, the expert layer by the pairs
a token finds here on average), and how the plain reference
(``benchmark/reference/kimi_linear.py``) judges what was served.  The two
layer lists of a configuration file are the published ones, whole.

**Serving only**, as ``deepseek_v3.py``: the training entries are absent
(neither the chunked scan's nor the expert layer's backward is part of the
program yet), and the harness asks for them only in a training cell.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import latent_moe_flops as F
from benchmark import linear_latent_flops as K
from benchmark.harness.spec import reference_module

R = reference_module(__file__, "kimi_linear")

PUBLISHED_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts_per_token", "num_shared_experts", "first_k_dense_replace",
    "moe_layer_freq", "moe_renormalize", "moe_router_activation_func",
    "num_expert_group", "topk_group", "use_grouped_topk", "routed_scaling_factor",
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "mla_use_nope", "rms_norm_eps", "rope_theta", "rope_scaling",
    "linear_attn_config", "tie_word_embeddings", "hidden_act")
MODEL_KEYS = PUBLISHED_KEYS + ("num_experts", "router_width", "experts_held")
_QKV = ("q", "k", "v")
_NOT_GAINS = ("e_score_correction_bias", "dt_bias", "A_log")


def sparse_layer(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


# -- leaves ---------------------------------------------------------------
def leaf_shapes(cfg: dict) -> dict:
    """name -> shape, in the names the program's state_dict uses (linear
    weights (in, out); a convolution's (channels, taps); a layer's held
    experts stacked over their number)."""
    H, V, nh = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    lin = cfg["linear_attn_config"]
    kh, kd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    D, r = kh * kd, kd              # the gates' rank: assumed the head size
    E, I = cfg["num_experts"], cfg["moe_intermediate_size"]
    if E != len(cfg["experts_held"]):
        raise ValueError(f"num_experts {E} counts the experts held, experts_held "
                         f"names {len(cfg['experts_held'])}")
    S = cfg["num_shared_experts"] * I
    shapes = {"model.embed_tokens.weight": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes[p + "input_layernorm.weight"] = (H,)
        if R.is_kda(cfg, i):
            for n in _QKV:
                shapes[p + f"self_attn.{n}_proj.weight"] = (H, D)
                shapes[p + f"self_attn.{n}_conv1d.weight"] = (D, taps)
            shapes[p + "self_attn.f_a_proj.weight"] = (H, r)
            shapes[p + "self_attn.f_b_proj.weight"] = (r, D)
            shapes[p + "self_attn.dt_bias"] = (D,)
            shapes[p + "self_attn.A_log"] = (kh,)
            shapes[p + "self_attn.b_proj.weight"] = (H, kh)
            shapes[p + "self_attn.g_a_proj.weight"] = (H, r)
            shapes[p + "self_attn.g_b_proj.weight"] = (r, D)
            shapes[p + "self_attn.o_norm.weight"] = (kd,)
            shapes[p + "self_attn.o_proj.weight"] = (D, H)
        else:
            shapes[p + "self_attn.q_proj.weight"] = (H, nh * (nope + rope))
            shapes[p + "self_attn.kv_a_proj_with_mqa.weight"] = (H, rank + rope)
            shapes[p + "self_attn.kv_a_layernorm.weight"] = (rank,)
            shapes[p + "self_attn.kv_b_proj.weight"] = (rank, nh * (nope + dv))
            shapes[p + "self_attn.o_proj.weight"] = (nh * dv, H)
        shapes[p + "post_attention_layernorm.weight"] = (H,)
        if sparse_layer(cfg, i):
            shapes[p + "mlp.gate.weight"] = (H, cfg["router_width"])
            shapes[p + "mlp.gate.e_score_correction_bias"] = (cfg["router_width"],)
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
    bias, ``dt_bias`` and ``A_log``, which are drawn about zero like a
    matrix (``dt_bias`` is then shifted: ``R.DT_BIAS_SHIFT``)."""
    return len(shape) == 1 and not name.endswith(_NOT_GAINS)


# -- the program ----------------------------------------------------------
def program_config(model: dict, max_positions: int):
    from paddle_tpu.models.nlp.kimi_linear import KimiLinearConfig
    return KimiLinearConfig(**{k: model[k] for k in PUBLISHED_KEYS},
                            num_experts=model["router_width"],
                            experts_held=tuple(model["experts_held"]),
                            max_position_embeddings=max_positions, dtype=jnp.bfloat16)


def serving_program(model: dict, engine: dict):
    """What ``ServingEngine`` takes as its model: shapes only, no weight
    made (``load_weights`` brings them)."""
    from paddle_tpu.models.nlp.kimi_linear import KimiLinearForCausalLM
    net = KimiLinearForCausalLM(program_config(model, engine["max_len"]))
    net.eval()
    return net


def load_weights(net, weights: dict):
    """The model keeps the drawn arrays themselves, ``dt_bias`` with the
    reference's one stated shift added; the harness's dict gives its
    references up."""
    net.load_tree({name: R.shift_decay(name, value) for name, value in weights.items()})
    weights.clear()


def drop_weights(net):
    net.drop_weights()


# -- operations -----------------------------------------------------------
def pairs_held_a_token(cfg: dict) -> float:
    """(token, expert) pairs a token finds among the held experts, on
    average over an even router."""
    return cfg["num_experts_per_token"] * cfg["num_experts"] / cfg["router_width"]


def token_matmul_params(cfg: dict) -> float:
    """Weights one position is multiplied by in all layers: a KDA layer's
    projections, convolution taps, two low-rank gates, ``W_b`` and ``W_o``;
    an MLA layer's (``W_q``, ``W_kva``, ``W_kvb``, ``W_o``); the dense
    layers' SwiGLU; in a sparse layer the router over its whole width, the
    held experts a token reaches on average and the shared expert."""
    H, nh, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lin = cfg["linear_attn_config"]
    D, r = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    kda = (4 * H * D + 3 * D * lin["short_conv_kernel_size"] + 2 * (H * r + r * D)
           + H * lin["num_heads"])
    mla = (H * nh * (nope + rope) + H * (rank + rope) + nh * nope * rank
           + nh * rank * dv + nh * dv * H)
    I = cfg["moe_intermediate_size"]
    sparse = (H * cfg["router_width"]
              + (pairs_held_a_token(cfg) + cfg["num_shared_experts"]) * 3 * H * I)
    dense = 3 * H * cfg["intermediate_size"]
    n_sparse = sum(sparse_layer(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return (K.kda_layers(cfg) * kda + K.mla_layers(cfg) * mla + n_sparse * sparse
            + (cfg["num_hidden_layers"] - n_sparse) * dense)


def forward_flops(cfg: dict, new_tokens: int, context_start: int,
                  head_tokens: int | None = None) -> float:
    """Forward pass of ``new_tokens`` tokens that follow ``context_start``
    cached ones; the head runs on ``head_tokens`` of them (all by default).
    A KDA layer costs the same a token whatever came before."""
    head_tokens = new_tokens if head_tokens is None else head_tokens
    body = (2.0 * token_matmul_params(cfg)
            + K.kda_layers(cfg) * K.kda_token_flops(cfg)) * new_tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    # token i (0-based) attends to context_start + i + 1 keys in an MLA layer
    pairs = new_tokens * context_start + new_tokens * (new_tokens + 1) / 2.0
    return body + head + F.expanded_pair_flops(cfg) * K.mla_layers(cfg) * pairs


def request_flops(cfg: dict, row: dict) -> tuple:
    """The passes one served request cost, from its own record: the prompt
    less what the prefix cache resumed past, the head on its last token
    alone; then one pass of one token for every output token after the first."""
    new = row["prompt_len"] - row["cached"]
    n_dec = len(row["token_times"]) - 1
    return (forward_flops(cfg, new, row["cached"], head_tokens=1),
            forward_flops(cfg, n_dec, row["prompt_len"]))


# -- the reference's judgement of what was served -------------------------
def pad_length(mix: dict) -> int:
    top = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    return -(-top // R.Q_BLOCK) * R.Q_BLOCK


def _layer_shape(model: dict, i: int):
    return R.is_kda(model, i), sparse_layer(model, i)


def reference_programs(model: dict, quant):
    """One compiled layer a distinct shape of layer (KDA or MLA, dense or
    sparse), the embedding and the head.  ``quant``: None, the control
    ``"int8"``, or a planted fault (``"state_dropped"``,
    ``"decay_ignored"``)."""
    layers = {}
    for i in range(model["num_hidden_layers"]):
        if _layer_shape(model, i) not in layers:
            layers[_layer_shape(model, i)] = jax.jit(partial(R.layer, model, i, quant=quant))

    def layer(i, w, x):
        return layers[_layer_shape(model, i)](w, x)

    mm_quant = quant if quant == "int8" else None

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def head(norm_w, head_w, x, rows):
        h = R.rms_norm(jnp.take(x, rows, axis=0), norm_w, model["rms_norm_eps"])
        return R._mm(h, head_w, mm_quant)
    return embed, layer, head


def reference_logits(model, programs, weights, tokens, rows):
    """Logits (len(rows), vocab) at positions ``rows`` of one padded sequence."""
    embed, layer, head = programs
    x = embed(weights["model.embed_tokens.weight"], tokens)
    for i in range(model["num_hidden_layers"]):
        x = layer(i, R.layer_weights(weights, i), x)
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
