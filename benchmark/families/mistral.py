"""The family of Mistral-7B-v0.3: a dense pre-norm decoder with grouped-query
causal attention, rotary positions, SwiGLU and an untied head, which the
program runs as a ``LlamaForCausalLM``.

The one place of the benchmark that knows this model: which keys of a
configuration file describe it, its leaves in the program's ``state_dict``
names, how the program is built for serving and for training, what a pass
costs, and how the plain reference (``benchmark/reference/mistral.py``)
judges what was served.  The harness finds all of it by the ``family`` a
configuration file names (benchmark/README.md, "A family").
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.flops import attention_flops_per_token
from benchmark.harness.spec import reference_module

R = reference_module(__file__, "mistral")

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
              "rope_theta", "tie_word_embeddings", "sliding_window")


# -- leaves ---------------------------------------------------------------
def leaf_shapes(cfg: dict) -> dict:
    """name -> shape, in the names the program's state_dict uses
    (linear weights are stored (in, out))."""
    H, I, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"model.embed_tokens.weight": (V, H)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes[p + "input_layernorm.weight"] = (H,)
        shapes[p + "self_attn.q_proj.weight"] = (H, q)
        shapes[p + "self_attn.k_proj.weight"] = (H, kv)
        shapes[p + "self_attn.v_proj.weight"] = (H, kv)
        shapes[p + "self_attn.o_proj.weight"] = (q, H)
        shapes[p + "post_attention_layernorm.weight"] = (H,)
        shapes[p + "mlp.gate_proj.weight"] = (H, I)
        shapes[p + "mlp.up_proj.weight"] = (H, I)
        shapes[p + "mlp.down_proj.weight"] = (I, H)
    shapes["model.norm.weight"] = (H,)
    shapes["lm_head.weight"] = (H, V)
    return shapes


def is_gain(name: str, shape) -> bool:
    """The norms' gains, the only leaves of rank one."""
    return len(shape) == 1


# -- the program ----------------------------------------------------------
def llama_config(model: dict, max_positions: int):
    from paddle_tpu.models.nlp import LlamaConfig
    if model["hidden_size"] != model["num_attention_heads"] * model["head_dim"]:
        raise ValueError("the program derives head_dim as hidden/heads")
    return LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_hidden_layers=model["num_hidden_layers"],
        num_attention_heads=model["num_attention_heads"],
        num_key_value_heads=model["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=model["rms_norm_eps"], rope_theta=model["rope_theta"],
        tie_word_embeddings=model["tie_word_embeddings"],
        sliding_window=model.get("sliding_window"), dtype=jnp.bfloat16)


def empty_model(model: dict, max_positions: int):
    """The program's model object with one-element placeholders for weights:
    its own float32 initial values (4 bytes a parameter, made leaf by leaf)
    are dropped at once."""
    from paddle_tpu.models.nlp import LlamaForCausalLM
    net = LlamaForCausalLM(llama_config(model, max_positions))
    drop_weights(net)
    net.eval()
    net.to(dtype="bfloat16")
    return net


def serving_program(model: dict, engine: dict):
    """What ``ServingEngine`` takes as its model, weights not yet loaded."""
    return empty_model(model, engine["max_len"])


def training_program(model: dict, job: dict):
    return empty_model(model, job["seq"])


def drop_weights(net):
    net.load_tree({k: jnp.zeros((1,), jnp.bfloat16) for k in net.state_dict()})


def load_weights(net, weights: dict):
    net.load_tree(weights)


def param_shardings(net, mesh) -> dict:
    from paddle_tpu.models.nlp.llama import param_shardings
    return param_shardings(net, mesh)


def train_step(net, mesh, job: dict):
    """``(params, optimizer state, step, the batch's sharding)`` of the
    compiled step, for the loaded ``net`` under the job's optimizer."""
    from paddle_tpu.models.nlp.llama import llama_train_step_factory
    hp = job["optimizer"]
    return llama_train_step_factory(
        net, mesh, learning_rate=hp["learning_rate"],
        weight_decay=hp["weight_decay"], beta1=hp["beta1"], beta2=hp["beta2"],
        eps=hp["eps"], accum_dtype=jnp.dtype(job["moments_dtype"]),
        remat=job["remat"])


# -- operations -----------------------------------------------------------
def layer_matmul_params(cfg: dict) -> int:
    H, I = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return H * q + 2 * H * kv + q * H + 3 * H * I


def matmul_params(cfg: dict) -> int:
    """Weights that every token is multiplied by: the layers and the head
    (the embedding is a lookup)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def forward_flops(cfg: dict, new_tokens: int, context_start: int,
                  head_tokens: int | None = None) -> float:
    """Forward pass of ``new_tokens`` tokens that follow ``context_start``
    cached ones; the head runs on ``head_tokens`` of them (all by default)."""
    head_tokens = new_tokens if head_tokens is None else head_tokens
    body = 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * new_tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * head_tokens
    # token i (0-based) attends to context_start + i + 1 keys
    ctx_sum = new_tokens * context_start + new_tokens * (new_tokens + 1) / 2.0
    return body + head + attention_flops_per_token(cfg, 1.0) * ctx_sum


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward pass."""
    return 3.0 * batch * forward_flops(cfg, seq, 0)


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
